"""Faults planted under the timed path, for the checks that ``correct``
comes out false (the tests) and for the fault readings that bound a
training number's limit (``calibrate.py readings --fault``).

Each is a function of the object a cell builds: the ``FOEMTrainer`` of a
training cell, the ``TopicServer`` of a serving cell.  One chip has no
exchange between chips, so that fault has no entry here.
"""
from __future__ import annotations

import numpy as np


def _wrap_step(trainer, wrap):
    """Wrap the trainer's compiled inner step, the call the window drives."""
    get = trainer._get_step_fn

    def faulty(shapes, refresh=False):
        fn = get(shapes, refresh)
        return lambda *args: wrap(fn, *args)

    trainer._get_step_fn = faulty


def train_state_unchanged(trainer):
    """The step returns the rows and totals it was given."""
    def wrap(fn, key, batch, rows, phi_k, *rest):
        out = fn(key, batch, rows.copy(), phi_k.copy(), *rest)
        return (rows, phi_k) + tuple(out[2:])

    _wrap_step(trainer, wrap)


def train_half_batch(trainer):
    """Half the documents left out, the rest counted double (the mean taken
    over the rest)."""
    def wrap(fn, key, batch, *rest):
        half = batch.counts.shape[0] // 2
        counts = batch.counts.at[:half].multiply(2.0).at[half:].set(0.0)
        return fn(key, batch._replace(counts=counts), *rest)

    _wrap_step(trainer, wrap)


def train_answer_altered(trainer):
    """One entry of the rows the step writes back is off by one count."""
    def wrap(fn, *args):
        rows, *out = fn(*args)
        return (rows.at[1, 0].add(1.0), *out)

    _wrap_step(trainer, wrap)


def serve_state_unchanged(server):
    """θ comes back as it started, uniform: no fixed-point sweep applied."""
    K = server.cfg.K
    server.infer = lambda w, c, key=None: np.full((w.shape[0], K), 1.0 / K,
                                                  np.float32)


def serve_half_batch(server):
    """Every other document of each launch left out (its slots padded)."""
    infer = server.infer

    def f(w, c, key=None):
        c = np.array(c)
        c[1::2] = 0.0
        return infer(w, c, key=key)

    server.infer = f


def serve_answer_altered(server):
    """The first answer of each launch has its topics rotated by one."""
    infer = server.infer

    def f(w, c, key=None):
        theta = np.array(infer(w, c, key=key))
        theta[0] = np.roll(theta[0], 1)
        return theta

    server.infer = f


TRAIN = {"state_unchanged": train_state_unchanged,
         "half_batch": train_half_batch,
         "answer_altered": train_answer_altered}
SERVE = {"state_unchanged": serve_state_unchanged,
         "half_batch": serve_half_batch,
         "answer_altered": serve_answer_altered}
