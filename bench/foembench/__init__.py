"""The FOEM chip benchmark: spec lookup, traffic, reference, reduction."""
