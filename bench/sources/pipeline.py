"""The program's input pipeline fed live: corpus -> pack -> staged ->
``OrderedPrefetchLoader`` -> ``DevicePrefetch``, with MLM masking in the
loader workers as ``launch/train.py`` does it.

The corpus is fixed by the traffic file and built once per checkout
under ``runs/bench/``.  ``--seed`` sets the order and the masking.  The
reference side derives each step's batch from the packed corpus on its
own: the epoch's permutation seeded by ``(seed, epoch)``, BERT masking
keyed by ``(seed, epoch, batch)``.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import bert_mlm


def corpus_dir(spec, root) -> Path:
    t, c = spec.traffic, spec.config
    return Path(root) / "runs" / "bench" / (
        f"corpus-{t['n_functions']}-{t['corpus_seed']}-{c['seq_len']}-"
        f"{c['vocab_size']}")


def build(spec, batch: int, seed: int, root, work_fn=None):
    """The program's DataPipeline over the traffic file's corpus."""
    from repro.data import DataPipeline, NetworkFS

    c, t, cell = spec.config, spec.traffic, spec.cell
    return DataPipeline.build(
        str(corpus_dir(spec, root)), n_functions=t["n_functions"],
        corpus_seed=t["corpus_seed"], seq_len=c["seq_len"],
        batch_size=batch, vocab_size=c["vocab_size"],
        network=NetworkFS(agg_bw=2e9, readers=8), seed=seed,
        n_workers=cell["loader_workers"], host_prefetch=t["host_prefetch"],
        device_prefetch=cell["device_prefetch"], work_fn=work_fn)


def expected(c, data_dir: Path, seed: int, batch: int, steps):
    """The reference's batch of each of ``steps``, from the packed corpus:
    the epoch's permutation seeded by ``(seed, epoch)``, BERT masking
    keyed by ``(seed, epoch, batch index)``."""
    with open(data_dir / "pipeline_build.json") as f:
        shards = json.load(f)["shards"]
    toks = np.concatenate([np.load(t) for t, _ in shards])
    attn = np.concatenate([np.load(m) for _, m in shards])
    per_epoch = len(toks) // batch
    out = []
    for step in steps:
        epoch, b = divmod(step, per_epoch)
        perm = np.random.default_rng([seed, epoch]).permutation(len(toks))
        rows = perm[b * batch:(b + 1) * batch]
        key = jax.random.PRNGKey(int(np.random.default_rng(
            [seed, epoch, b]).integers(1 << 30)))
        inputs, labels, sel = bert_mlm.mask_tokens(
            key, jnp.asarray(toks[rows].astype(np.int32)), c)
        out.append({"tokens": np.asarray(inputs),
                    "labels": np.asarray(labels),
                    "loss_mask": np.asarray(sel)
                    * attn[rows].astype(np.float32)})
    return out


class Pipeline:
    device_prefetch = True

    def __init__(self, spec, runner, seed: int, root: Path):
        from repro.core.mlm import mask_tokens

        c = spec.config
        self.c, self.seed = c, seed
        self.batch = runner.run.shape.global_batch
        V, mask_id = c["vocab_size"], c["mask"]["mask_id"]

        def work(batch, rng):
            key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
            inputs, labels, mask = mask_tokens(
                key, jnp.asarray(batch["tokens"]), V, mask_id=mask_id)
            return {"tokens": np.asarray(inputs),
                    "labels": np.asarray(labels),
                    "loss_mask": np.asarray(mask) * batch["attn_mask"]}

        self.data_dir = corpus_dir(spec, root)
        self.pipe = build(spec, self.batch, seed, root, work)

    def batches(self):
        return self.pipe.host_batches()

    def reference_batches(self, n: int):
        return expected(self.c, self.data_dir, self.seed, self.batch,
                        range(n))

    def check(self, kept):
        """``batch_mismatch``: consumed batches that differ in any entry
        from the reference's batch of the same step."""
        steps = sorted(kept)
        want = expected(self.c, self.data_dir, self.seed, self.batch, steps)
        bad = sum(any(not np.array_equal(np.asarray(kept[s][k]), w[k])
                      for k in w) for s, w in zip(steps, want))
        return {"batch_mismatch": float(bad)}

    def close(self):
        self.pipe.close()


def open_source(spec, runner, seed: int, root):
    return Pipeline(spec, runner, seed, root)


def control_batches(spec, batch: int, seed: int, root, n: int):
    """The batches of the first ``n`` steps, for the control."""
    build(spec, batch, seed, root).close()
    return expected(spec.config, corpus_dir(spec, root), seed, batch,
                    range(n))
