"""Faults planted underneath the timed path, to see ``correct`` come out
false; never used by a run of the benchmark.

* ``state_unchanged``: the decode step returns the cache it was given;
* ``half_batch``: the decode step's logits of the upper half of the
  slots are replaced by those of the lower half;
* ``token_altered``: every seventh token is altered where the sampler
  produces it.

``planted(kind)`` patches the program for the duration of a ``with``
block; the engine has to be built inside it.
"""
from __future__ import annotations

import contextlib

import numpy as np

KINDS = ("state_unchanged", "half_batch", "token_altered")


def _step_fault(real, kind: str):
    def step(params, cfg, cache, tokens, rt, **kw):
        new, logits = real(params, cfg, cache, tokens, rt, **kw)
        if kind == "state_unchanged":
            return cache, logits
        half = logits.shape[0] // 2
        return new, logits.at[half:].set(logits[:half])
    return step


def _token_fault(real):
    calls = []

    def sample(self, logits, rng=None):
        tok = real(self, logits, rng)
        calls.append(tok)
        return (tok + 1) % len(np.ravel(logits)) if len(calls) % 7 == 3 \
            else tok
    return sample


@contextlib.contextmanager
def planted(kind):
    """The program with fault ``kind`` (or none, for ``None``)."""
    from repro.serve import paged, sampling
    if kind is None:
        yield
        return
    if kind in ("state_unchanged", "half_batch"):
        owner, name = paged, "decode_step_paged"
        fake = _step_fault(paged.decode_step_paged, kind)
    elif kind == "token_altered":
        owner, name = sampling.Sampler, "sample"
        fake = _token_fault(sampling.Sampler.sample)
    else:
        raise ValueError(f"unknown fault {kind!r}; known: {KINDS}")
    real = getattr(owner, name)
    setattr(owner, name, fake)
    try:
        yield
    finally:
        setattr(owner, name, real)
