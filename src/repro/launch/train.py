"""Training driver.

Two modes:
  * ``--arch foem-lda`` — the paper's system: streaming FOEM with the
    disk-backed ParameterStore (single-host runtime; pjit path available via
    --device-resident for corpora whose φ̂ fits device memory).
  * ``--arch <lm-arch>`` — reduced-config LM training on synthetic token
    streams (the end-to-end substrate exercise; production sizes are
    dry-run-only on CPU).

Fault tolerance: checkpoints every --ckpt-every steps (atomic, resharding-
capable); ``--resume`` restarts from the latest checkpoint + data cursor.
Kill the process mid-run and relaunch with --resume to see it.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs.registry import ARCHS, LDA_ARCH
from repro.core import FOEMTrainer, LDAConfig, ParameterStore
from repro.core.perplexity import predictive_perplexity, split_heldout_counts
from repro.core.types import MinibatchData
from repro.data import synthetic_lda_corpus, synthetic_token_stream
from repro.models import build
from repro.optim import adamw_init, adamw_update, cosine_warmup
from repro.runtime.compile_cache import enable_compile_cache
from repro.sparse import MinibatchStream
from repro.sparse.docword import bucketize


def train_lda(args) -> None:
    cfg = LDAConfig(
        num_topics=args.topics,
        vocab_size=args.vocab,
        active_topics=args.active_topics,
        iem_blocks=args.iem_blocks,
        max_sweeps=args.max_sweeps,
    )
    corpus, _ = synthetic_lda_corpus(
        args.docs, args.vocab, args.topics_true or args.topics,
        mean_doc_len=args.doc_len, seed=args.seed,
    )
    rng = np.random.default_rng(args.seed)
    train, test = corpus.split_train_test(max(args.docs // 10, 8), rng)
    store = ParameterStore(
        args.workdir, num_topics=args.topics, vocab_capacity=args.vocab,
        buffer_rows=args.buffer_rows,
    )
    trainer = FOEMTrainer(
        cfg, store, seed=args.seed, checkpoint_every=args.ckpt_every,
        algorithm=args.algorithm, prefetch_depth=args.prefetch_depth,
    )
    start = trainer.resume_step() if args.resume else 0
    if start:
        print(f"[resume] continuing from minibatch cursor {start}")
    stream = MinibatchStream(
        train, args.minibatch, seed=args.seed + start, epochs=None
    )
    t0 = time.time()

    def report(m):
        if m.step % args.log_every == 0:
            pf = "+" if m.prefetch_hit else "-"
            print(
                f"step {m.step:5d} sweeps={m.sweeps:2d} "
                f"train_ppl={m.train_ppl:9.2f} io r/w={m.disk_reads}/"
                f"{m.disk_writes} hits={m.buffer_hits} pf{pf} "
                f"overlap={m.overlap_seconds*1e3:5.1f}ms {m.seconds:5.2f}s"
            )

    trainer.fit_stream(iter(stream), max_steps=args.steps, callback=report)
    print(f"trained {args.steps} minibatches in {time.time()-t0:.1f}s")

    # held-out predictive perplexity (paper eq. 21)
    ids = list(range(test.num_docs))
    w, c = bucketize(test, ids)
    est_c, ev_c = split_heldout_counts(c, rng)
    phi = jnp.asarray(store.dense_phi())
    pad = cfg.W - phi.shape[0]
    if pad > 0:
        phi = jnp.pad(phi, ((0, pad), (0, 0)))
    ppl = predictive_perplexity(
        jax.random.PRNGKey(0),
        MinibatchData(jnp.asarray(w), jnp.asarray(est_c)),
        MinibatchData(jnp.asarray(w), jnp.asarray(ev_c)),
        phi, jnp.asarray(store.phi_k, jnp.float32), cfg,
    )
    print(f"predictive perplexity (eq. 21): {float(ppl):.2f}")


def train_lm(args) -> None:
    cfg = ARCHS[args.arch].reduced()
    model = build(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init_params(key)
    opt = adamw_init(params)
    step0 = 0
    if args.resume and latest_step(args.workdir) is not None:
        step0, (params, opt) = restore_checkpoint(args.workdir, (params, opt))
        print(f"[resume] from step {step0}")

    @jax.jit
    def train_step(params, opt, batch):
        loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
        lr = cosine_warmup(opt.count, peak_lr=1e-3, warmup=20, total=args.steps)
        params, opt = adamw_update(grads, opt, params, lr=lr)
        return loss, params, opt

    stream = synthetic_token_stream(
        args.minibatch, args.seq_len, cfg.vocab_size, seed=args.seed + step0
    )
    t0 = time.time()
    for step in range(step0 + 1, args.steps + 1):
        batch = next(stream)
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        if cfg.frontend == "audio_frames":
            b["embeds"] = jax.nn.one_hot(
                b.pop("tokens") % cfg.d_model, cfg.d_model, dtype=jnp.float32
            )
        if cfg.frontend == "image_patches":
            b["image_embeds"] = jnp.ones(
                (args.minibatch, cfg.image_tokens, cfg.d_model), jnp.float32
            ) * 0.01
        loss, params, opt = train_step(params, opt, b)
        if step % args.log_every == 0:
            print(f"step {step:5d} loss={float(loss):8.4f} "
                  f"({(time.time()-t0)/step:.2f}s/step)")
        if args.ckpt_every and step % args.ckpt_every == 0:
            save_checkpoint(args.workdir, step, (params, opt))
    print(f"done: {args.steps} steps in {time.time()-t0:.1f}s")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=LDA_ARCH)
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--log-every", type=int, default=1)
    # LDA options
    ap.add_argument("--algorithm", default="foem", choices=["foem", "sem"])
    ap.add_argument("--topics", type=int, default=100)
    ap.add_argument("--topics-true", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=5000)
    ap.add_argument("--docs", type=int, default=2000)
    ap.add_argument("--doc-len", type=int, default=80)
    ap.add_argument("--minibatch", type=int, default=256)
    ap.add_argument("--active-topics", type=int, default=16)
    ap.add_argument("--max-sweeps", type=int, default=24)
    ap.add_argument("--iem-blocks", type=int, default=0,
                    help="0 = column-serial IEM folds (paper-faithful)")
    ap.add_argument("--buffer-rows", type=int, default=2048)
    ap.add_argument("--prefetch-depth", type=int, default=1,
                    help="minibatches fetched ahead of the device "
                         "(0 = synchronous host I/O)")
    # LM options
    ap.add_argument("--seq-len", type=int, default=128)
    args = ap.parse_args()
    os.makedirs(args.workdir, exist_ok=True)
    if args.arch == LDA_ARCH:
        train_lda(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
