"""Elastic topology-resharding restore (``distributed/reshard.py``).

Three layers, cheapest first:

* File-level N->M matrix: synthetic checkpoints in the real sub-shard
  layout (``SubShardLeaf.from_parts`` + ``save_sharded``) written as if
  by {1,2,4} processes under ddp / fsdp / pp-style leaf layouts, then
  reassembled for {1,2,4} target processes — every target region must
  come back bit-exact, reading only the overlapping stored parts.

* Property test (``tests/_hypothesis_compat``): random shapes, random
  uneven splits, random process assignment — reassembly == original.

* End-to-end acceptance (slow, subprocesses via ``tests/_faults.py``):
  per plan (ddp / fsdp / demoted-pp), a 2-process sub-shard checkpoint
  restores through ``resume_resharded`` onto the 1-process 4-device
  mesh with bit-exact params/optimizer moments and the uninterrupted
  run's exact loss trajectory, and every shard a 4-process target
  would read comes back bit-exact.  XLA's CPU backend refuses to
  compile multi-process computations, so the 2-process layout is
  materialized from the reference state via the plan's own
  device->index maps (byte-identical to what a real 2-process run
  stores — that save path itself is proven with real
  ``jax.distributed`` processes in ``test_subshard_ckpt.py``).

  Plus the rollback-journal acceptance: a worker killed mid-step by an
  armed fault recovers from its tmpfs journal — no disk checkpoint
  anywhere in the run.
"""
import json
import os
import tempfile

import numpy as np
import pytest

import jax

from _faults import FAULT_EXIT_CODE, fault_env, read_kill_log, run_one
from _hypothesis_compat import given, settings, st

from repro.distributed import reshard
from repro.train import checkpoint as ckpt


# ---------------------------------------------------------------------------
# file-level N->M matrix
# ---------------------------------------------------------------------------


def _state():
    rng = np.random.default_rng(11)
    return {
        "params": {"w": rng.normal(size=(16, 6)).astype(np.float32),
                   "stacked": rng.normal(size=(4, 8, 3)).astype(np.float32),
                   "b": rng.normal(size=(5,)).astype(np.float32)},
        "opt": {"mu": rng.normal(size=(16, 6)).astype(np.float32),
                "nu": rng.normal(size=(16, 6)).astype(np.float32),
                "step": np.int32(9)},
    }


def _rows(key):
    # dim-0-sharded leaves under fsdp; everything else replicated
    return key in ("params/w", "opt/mu", "opt/nu")


def _save_matrix_ckpt(base, state, plan, n_procs, *, step=3):
    """Write ``state`` as ``n_procs`` shard files in the layout the
    given plan produces: fsdp dim-0-shards the big leaves (2 'devices'
    per process), pp stage-shards the stacked leaf, ddp replicates
    everything (cross-process replication = one full-coverage sub-shard
    per process, exactly what ``SubShardLeaf`` stores)."""
    flat = {"/".join(["params", k]): v for k, v in state["params"].items()}
    flat.update({"/".join(["opt", k]): v for k, v in state["opt"].items()})
    for pidx in range(n_procs):
        tree = {"params": {}, "opt": {}}
        for key, arr in flat.items():
            group, name = key.split("/")
            if n_procs == 1:
                tree[group][name] = arr  # fully addressable: plain leaf
                continue
            if plan == "fsdp" and _rows(key):
                n_parts = n_procs * 2  # two local devices per process
                starts = np.linspace(0, arr.shape[0], n_parts + 1,
                                     dtype=int)
                parts = [((int(starts[i]),) + (0,) * (arr.ndim - 1),
                          arr[starts[i]:starts[i + 1]])
                         for i in range(pidx * 2, pidx * 2 + 2)]
                tree[group][name] = ckpt.SubShardLeaf.from_parts(
                    arr.shape, parts)
            elif plan == "pp" and key == "params/stacked":
                stages = np.linspace(0, arr.shape[0], n_procs + 1,
                                     dtype=int)
                lo, hi = int(stages[pidx]), int(stages[pidx + 1])
                tree[group][name] = ckpt.SubShardLeaf.from_parts(
                    arr.shape,
                    [((lo,) + (0,) * (arr.ndim - 1), arr[lo:hi])])
            elif arr.ndim == 0:
                tree[group][name] = arr  # scalars stay plain
            else:
                # replicated cross-process leaf: one full-coverage part
                tree[group][name] = ckpt.SubShardLeaf.from_parts(
                    arr.shape, [((0,) * arr.ndim, arr)])
        ckpt.save_sharded(base, tree, step=step, process_index=pidx,
                          process_count=n_procs)


def _target_region(key, arr, plan, m_procs, t):
    """The region target process ``t`` of ``m_procs`` owns under the
    restore-side plan."""
    if m_procs == 1 or arr.ndim == 0:
        return tuple(slice(0, n) for n in arr.shape)
    if plan == "fsdp" and _rows(key):
        starts = np.linspace(0, arr.shape[0], m_procs + 1, dtype=int)
        return (slice(int(starts[t]), int(starts[t + 1])),) + tuple(
            slice(0, n) for n in arr.shape[1:])
    if plan == "pp" and key == "params/stacked":
        stages = np.linspace(0, arr.shape[0], m_procs + 1, dtype=int)
        return (slice(int(stages[t]), int(stages[t + 1])),) + tuple(
            slice(0, n) for n in arr.shape[1:])
    return tuple(slice(0, n) for n in arr.shape)  # replicated: read whole


@pytest.mark.parametrize("plan", ["ddp", "fsdp", "pp"])
@pytest.mark.parametrize("save_n", [1, 2, 4])
@pytest.mark.parametrize("restore_m", [1, 2, 4])
def test_reshard_matrix_bit_exact(tmp_path, plan, save_n, restore_m):
    state = _state()
    base = str(tmp_path / f"{plan}-{save_n}")
    _save_matrix_ckpt(base, state, plan, save_n)
    flat = {f"params/{k}": v for k, v in state["params"].items()}
    flat.update({f"opt/{k}": v for k, v in state["opt"].items()})
    with reshard.CheckpointLayout.scan(base) as lay:
        assert lay.step == 3 and lay.process_count == save_n
        for t in range(restore_m):
            for key, arr in flat.items():
                reg = _target_region(key, arr, plan, restore_m, t)
                got = lay.read_region(key, reg if arr.ndim else None)
                np.testing.assert_array_equal(got, arr[reg] if arr.ndim
                                              else arr)


def test_reshard_reads_only_overlapping_parts(tmp_path):
    """The elastic claim: a narrow target region touches exactly the
    stored parts that overlap it, not the whole leaf."""
    state = _state()
    base = str(tmp_path / "ck")
    _save_matrix_ckpt(base, state, "fsdp", 4)  # w stored as 8 row-parts
    with reshard.CheckpointLayout.scan(base) as lay:
        region = (slice(0, 2), slice(0, 6))  # first row-part only
        assert len(lay.covering_parts("params/w", region)) == 1
        region = (slice(0, 4), slice(0, 6))  # first two row-parts
        assert len(lay.covering_parts("params/w", region)) == 2
        all_parts = lay.covering_parts("params/w",
                                       (slice(0, 16), slice(0, 6)))
        assert len(all_parts) == 8


def test_reshard_detects_coverage_gap(tmp_path):
    """A lost shard's rows must fail loudly, not restore as zeros."""
    state = _state()
    base = str(tmp_path / "ck")
    _save_matrix_ckpt(base, state, "fsdp", 2)
    # drop process 1's sub-shards of w from its npz by rewriting the
    # sidecar to claim fewer parts -> rows [8,16) are gone
    import json as _json
    sj = os.path.join(ckpt.step_dir(base, 3), "shard-00001.subshards.json")
    with open(sj) as f:
        subs = _json.load(f)
    subs["params/w"]["parts"] = []
    with open(sj, "w") as f:
        _json.dump(subs, f)
    with reshard.CheckpointLayout.scan(base) as lay:
        with pytest.raises(ValueError, match="gap|cover"):
            lay.read_region("params/w", (slice(0, 16), slice(0, 6)))
        # the intact half still reads fine
        got = lay.read_region("params/w", (slice(0, 8), slice(0, 6)))
        np.testing.assert_array_equal(got, state["params"]["w"][:8])


def test_restore_resharded_tree_and_pipeline_state(tmp_path):
    state = _state()
    base = str(tmp_path / "ck")
    _save_matrix_ckpt(base, state, "fsdp", 2)
    like = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), state)
    tree, pstate, manifest = reshard.restore_resharded(base, like)
    assert manifest["process_count"] == 2
    for got, want in zip(jax.tree_util.tree_leaves(tree),
                         jax.tree_util.tree_leaves(state)):
        np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# property test: random shapes / splits / process assignment
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(rows=st.integers(min_value=3, max_value=24),
       cols=st.integers(min_value=1, max_value=7),
       n_parts=st.integers(min_value=1, max_value=5),
       n_procs=st.integers(min_value=1, max_value=3))
def test_subshard_reassembly_roundtrip(rows, cols, n_parts, n_procs):
    # one fresh directory per example: a function-scoped tmp_path would
    # be shared by every example hypothesis generates
    with tempfile.TemporaryDirectory() as tmp:
        n_parts = min(n_parts, rows)
        rng = np.random.default_rng([rows, cols, n_parts, n_procs])
        arr = rng.normal(size=(rows, cols)).astype(np.float32)
        cuts = np.linspace(0, rows, n_parts + 1, dtype=int)
        per_proc = [[] for _ in range(n_procs)]
        for i in range(n_parts):
            lo, hi = int(cuts[i]), int(cuts[i + 1])
            if lo == hi:
                continue
            per_proc[i % n_procs].append(((lo, 0), arr[lo:hi]))
        base = os.path.join(tmp, f"p{rows}x{cols}-{n_parts}-{n_procs}")
        for pidx in range(n_procs):
            tree = {"w": ckpt.SubShardLeaf.from_parts(arr.shape,
                                                      per_proc[pidx])} \
                if per_proc[pidx] else {"pad": np.float32(0.0)}
            ckpt.save_sharded(base, tree, step=1, process_index=pidx,
                              process_count=n_procs)
        with reshard.CheckpointLayout.scan(base) as lay:
            np.testing.assert_array_equal(lay.read_region("w"), arr)
            # an arbitrary interior region reassembles across part seams
            r0, r1 = rows // 3, max(rows // 3 + 1, (2 * rows) // 3)
            got = lay.read_region("w", (slice(r0, r1), slice(0, cols)))
            np.testing.assert_array_equal(got, arr[r0:r1])


# ---------------------------------------------------------------------------
# end-to-end: real workers, real plans (slow)
# ---------------------------------------------------------------------------

E2E_COMMON = """
    import dataclasses, json, os, sys
    import numpy as np
    import jax

    from repro.configs import get_config, reduced
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.data import DataPipeline
    from repro.launch.mesh import make_host_mesh
    from repro.train.optimizer import AdamWConfig
    from repro.train.runner import (StepRunner, TrainLoop, resume,
                                    resume_resharded)
    from repro.models import build_model

    TMP = os.environ["RESHARD_TMP"]
    PLAN = os.environ["RESHARD_PLAN"]
    SEQ, GB, STEPS, HALF = 32, 8, 8, 3
    cfg = dataclasses.replace(reduced(get_config("bert-mlm-120m"),
                                      d_model=64),
                              vocab_size=512, max_position=SEQ)
    model = build_model(cfg)
    run = RunConfig(model=cfg, shape=ShapeConfig("t", SEQ, GB, "train"),
                    sharding=PLAN, param_dtype="float32",
                    activation_dtype="float32")

    def work(batch, rng):
        toks = batch["tokens"]
        return {"tokens": toks, "labels": np.roll(toks, -1, axis=1),
                "loss_mask": batch["attn_mask"]}

    def make_pipe(pidx=0, pcount=1):
        return DataPipeline.build(os.path.join(TMP, "data-%d-%d"
                                               % (pidx, pcount)),
                                  n_functions=150, seq_len=SEQ,
                                  batch_size=GB // pcount, vocab_size=512,
                                  max_merges=60, n_workers=2, seed=3,
                                  process_index=pidx,
                                  process_count=pcount, work_fn=work)

    def make_runner():
        opt = AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=STEPS)
        return StepRunner(model, run, opt,
                          make_host_mesh(data=len(jax.devices())))

    CK = os.path.join(TMP, "ck-" + PLAN)
    REF_CK = os.path.join(TMP, "refck-" + PLAN)
    REF_JSON = os.path.join(TMP, "ref-" + PLAN + ".json")
"""

E2E_BODY = E2E_COMMON + """
    from jax.tree_util import (tree_flatten, tree_flatten_with_path,
                               tree_leaves, tree_unflatten)

    from repro.distributed import reshard
    from repro.train import checkpoint as ckpt
    from repro.train.train_step import abstract_state

    # --- phase 1: uninterrupted reference on the 4-device mesh --------
    p = make_pipe()
    r = make_runner()
    _, log = TrainLoop(r, log_every=1, ckpt_dir=REF_CK, ckpt_every=HALF,
                       async_checkpoint=False).run(p, STEPS, seed=0)
    p.close()
    ref_losses = [m["loss"] for m in log.metrics]
    assert len(ref_losses) == STEPS

    # --- phase 2: materialize step HALF as a 2-process sub-shard
    # checkpoint.  XLA's CPU backend cannot COMPILE multi-process
    # computations, so the 2-process layout is derived from the
    # reference state via the plan's own device->index maps: the 4
    # devices grouped into 2 fake processes of 2, each storing exactly
    # the deduplicated slices a real 2-process run stores (that save
    # path is proven with real jax.distributed in test_subshard_ckpt).
    like = abstract_state(model, run)
    ref_tree, _, _ = ckpt.restore_sharded(REF_CK, like, step=HALF)
    host = jax.tree_util.tree_map(np.asarray, ref_tree)
    flat, treedef = tree_flatten(host)
    sh_flat = tree_leaves(r.state_shardings)
    assert len(flat) == len(sh_flat)
    devs = list(jax.devices())
    NP = 2
    proc_of = {id(d): i // (len(devs) // NP) for i, d in enumerate(devs)}
    for pidx in range(NP):
        leaves = []
        for arr, sh in zip(flat, sh_flat):
            if arr.ndim == 0:
                leaves.append(arr)
                continue
            parts, seen = [], set()
            for d, idx in sh.devices_indices_map(arr.shape).items():
                if proc_of[id(d)] != pidx:
                    continue
                sub = arr[idx]
                start = tuple(int(s.start or 0) for s in idx)
                if (start, sub.shape) in seen:
                    continue  # local replicas dedup, like save_sharded
                seen.add((start, sub.shape))
                parts.append((start, sub))
            leaves.append(ckpt.SubShardLeaf.from_parts(arr.shape, parts))
        pview = make_pipe(pidx, NP)  # the 2-process run's data cursor
        ckpt.save_sharded(CK, tree_unflatten(treedef, leaves), step=HALF,
                          process_index=pidx, process_count=NP,
                          pipeline_state=pview.state_at(HALF).to_json())
        pview.close()

    # --- phase 3: every shard a 4-process (1 device each) target would
    # own reads back bit-exact from the 2-process layout ---------------
    kv, _ = tree_flatten_with_path(host)
    keys = [ckpt.leaf_key(path) for path, _ in kv]
    with reshard.CheckpointLayout.scan(CK) as lay:
        assert lay.step == HALF and lay.process_count == NP
        for key, arr, sh in zip(keys, flat, sh_flat):
            if arr.ndim == 0:
                continue
            for d, idx in sh.devices_indices_map(arr.shape).items():
                np.testing.assert_array_equal(
                    lay.read_region(key, idx), arr[idx])
    print("4-process target regions OK", flush=True)

    # --- phase 4: the product path — elastic restore onto the
    # 1-process mesh, bit-exact state, exact continued trajectory ------
    p2 = make_pipe()
    r2 = make_runner()
    state, start = resume_resharded(CK, r2, pipeline=p2)
    assert start == HALF
    for a, b in zip(tree_leaves(state), flat):
        np.testing.assert_array_equal(np.asarray(a), b)
    _, log2 = TrainLoop(r2, log_every=1).run(p2, STEPS, state=state,
                                             start_step=start)
    p2.close()
    losses = [m["loss"] for m in log2.metrics]
    assert losses == ref_losses[HALF:], (losses, ref_losses[HALF:])
    print("elastic restore OK", flush=True)
"""


@pytest.mark.slow
@pytest.mark.parametrize("plan", ["ddp", "fsdp", "pp"])
def test_elastic_restore_2proc_ckpt_onto_1_and_4proc(tmp_path, plan):
    """A 2-process checkpoint restores onto the 1-process 4-device mesh
    through ``resume_resharded`` with bit-exact params/moments and the
    uninterrupted run's exact 5-step continued loss trajectory, and
    every region a 4-process target would own reads back bit-exact.
    ``pp`` on this mesh is the demoted-pp layout (no pipe axis)."""
    env = {"RESHARD_TMP": str(tmp_path), "RESHARD_PLAN": plan}
    out = run_one(E2E_BODY, extra_env=env, n_devices=4)
    assert "4-process target regions OK" in out
    assert "elastic restore OK" in out


# ---------------------------------------------------------------------------
# rollback journal: kill mid-step, recover without a disk checkpoint
# ---------------------------------------------------------------------------

JOURNAL_COMMON = E2E_COMMON + """
    from repro.train.journal import RollbackJournal

    JDIR = os.environ["RESHARD_JDIR"]
"""

JOURNAL_REF = JOURNAL_COMMON + """
    p = make_pipe()
    _, log = TrainLoop(make_runner(), log_every=1).run(p, STEPS, seed=0)
    p.close()
    with open(REF_JSON, "w") as f:
        json.dump([m["loss"] for m in log.metrics], f)
    print("ref OK")
"""

JOURNAL_KILLED = JOURNAL_COMMON + """
    # NO ckpt_dir anywhere: the tmpfs journal is the only redundancy.
    # The armed `step` fault kills this process right after dispatching
    # step 5; the journal's newest complete entry is step 5.
    p = make_pipe()
    loop = TrainLoop(make_runner(), log_every=1,
                     journal=RollbackJournal(2, dir=JDIR))
    loop.run(p, STEPS, seed=0)
    raise SystemExit("fault point did not fire")
"""

JOURNAL_RESTART = JOURNAL_COMMON + """
    # a journal entry IS a sharded checkpoint (in tmpfs): the ordinary
    # resume path restores it — no on-disk checkpoint ever existed
    p = make_pipe()
    r = make_runner()
    state, start = resume(JDIR, r, pipeline=p)
    assert start == 5, start
    _, log = TrainLoop(r, log_every=1).run(p, STEPS, state=state,
                                           start_step=start)
    p.close()
    with open(REF_JSON) as f:
        ref = json.load(f)
    losses = [m["loss"] for m in log.metrics]
    assert losses == ref[start:], (losses, ref[start:])
    print("journal restart OK")
"""


@pytest.mark.slow
def test_worker_killed_mid_step_recovers_from_tmpfs_journal(tmp_path):
    shm = "/dev/shm" if os.path.isdir("/dev/shm") else str(tmp_path)
    import tempfile

    jdir = tempfile.mkdtemp(prefix="repro-journal-", dir=shm)
    try:
        env = {"RESHARD_TMP": str(tmp_path), "RESHARD_PLAN": "ddp",
               "RESHARD_JDIR": jdir}
        assert "ref OK" in run_one(JOURNAL_REF, extra_env=env,
                                   n_devices=4)
        log = str(tmp_path / "kill.log")
        run_one(JOURNAL_KILLED, extra_env={
            **env, **fault_env("step", step=5, log=log)},
            n_devices=4, expect_exit=FAULT_EXIT_CODE)
        rec = read_kill_log(log)
        assert rec["phase"] == "step" and rec["step"] == "5"
        # nothing was ever written outside tmpfs
        assert not os.path.exists(os.path.join(str(tmp_path), "ck-ddp"))
        assert "journal restart OK" in run_one(JOURNAL_RESTART,
                                               extra_env=env,
                                               n_devices=4)
    finally:
        import shutil

        shutil.rmtree(jdir, ignore_errors=True)
