"""A ring of distinct batches made once from the seed and kept on the
device: no loader and no corpus, so the step program does the work.

Token ids are uniform over the maskable vocabulary, masked by BERT's
recipe (``bench.reference.bert_mlm.mask_tokens``): the benchmark makes
the inputs, as it makes the weights.  The ring is cycled.
"""
from __future__ import annotations

import itertools

import jax

from bench.reference.bert_mlm import mask_tokens
from bench.reference.core import seed_key

RING_KEY = 1_000_003  # folded into the seed's key: apart from the weights'


def ring(spec, batch: int, seed: int, shardings=None):
    """The ring's batches on the device, made in one call from ``seed``."""
    c, n = spec.config, spec.traffic["ring"]
    S, V, m = c["seq_len"], c["vocab_size"], c["mask"]

    def make(key):
        key = jax.random.fold_in(key, RING_KEY)
        out = []
        for i in range(n):
            k1, k2 = jax.random.split(jax.random.fold_in(key, i))
            toks = jax.random.randint(k1, (batch, S), m["special_boundary"],
                                      V)
            inputs, labels, sel = mask_tokens(k2, toks, c)
            out.append({"tokens": inputs, "labels": labels,
                        "loss_mask": sel})
        return out

    return jax.jit(make, out_shardings=None if shardings is None
                   else [shardings] * n)(seed_key(seed))


class Resident:
    device_prefetch = False

    def __init__(self, spec, runner, seed: int):
        sh = {k: runner.batch_shardings[k]
              for k in ("tokens", "labels", "loss_mask")}
        self.ring = ring(spec, runner.run.shape.global_batch, seed, sh)

    def batches(self):
        return itertools.cycle(self.ring)

    def reference_batches(self, n: int):
        return [jax.device_get(self.ring[i % len(self.ring)])
                for i in range(n)]

    def check(self, kept):
        return {}

    def close(self):
        self.ring = None


def open_source(spec, runner, seed: int, root):
    return Resident(spec, runner, seed)


def control_batches(spec, batch: int, seed: int, root, n: int):
    """The batches of the first ``n`` steps, for the control."""
    r = ring(spec, batch, seed)
    return [jax.device_get(r[i % len(r)]) for i in range(n)]
