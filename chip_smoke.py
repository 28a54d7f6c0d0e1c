"""Prove that the training path runs on the TPU.

    python chip_smoke.py             # one chip: train, resume, kernels
    python chip_smoke.py --chips 4   # four chips: ddp and fsdp vs one device

Everything runs in this one process, the only one that touches JAX; the
training phases call the launcher's own ``repro.launch.train.main``.

* train   -- bert-mlm-120m at its published width (12L, d_model 768,
             vocab 32768) at seq 512, batch 32, default ddp sharding and
             worker auto-tune, 20 steps with a checkpoint every 10.  The
             loss is finite and falls, the step compiles once, and the
             state lives on the chip.
* resume  -- the same command with ``--resume --ckpt-step 10``; steps
             11-20 match the uninterrupted run.
* kernels -- each Pallas kernel, compiled, at one real width against its
             ``kernels/ref.py`` oracle.
* chips 4 -- five steps at global batch 64 under ``--sharding ddp`` and
             ``--sharding fsdp`` on four chips, against the same batch and
             seed on one of them, at the "highest" matmul precision; the
             params are laid out as the plan says.

The last line of standard output is ``{"ok": true, "device": ...}`` only
when every phase passed on a TPU.  Without a TPU, or when a phase fails,
the script exits non-zero without it.  Checkpoints and the synthesized
corpus go to ``runs/chip_smoke/`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ref as kref  # noqa: E402

RUN_DIR = ROOT / "runs" / "chip_smoke"
TRAIN_STEPS = 20
RESUME_FROM = 10
# resumed losses match the uninterrupted run within this relative bound
RESUME_RTOL = 1e-6
# kernels take bf16 inputs and are compared with an f32 oracle on the same
# inputs: |kernel - oracle| <= BF16_TOL * max(1, max|oracle|), a few bf16
# roundings (2^-8 each) of the largest output
BF16_TOL = 2e-2
# ddp, fsdp and one device are compared at the "highest" matmul precision.
# At the TPU's default precision f32 matmuls round their operands to bf16,
# and Adam's sign-like first updates amplify that noise from step to step
# (ddp vs one device: 2.8e-5 at step 1, 5.2e-2 at step 5, on a v5e).  At
# "highest" the paths differ only in f32 summation order: 2.0e-6 in the
# step-1 loss and grad norm, 4.9e-7 over five steps of loss (v5e)
STEP1_RTOL = 1e-4
MULTICHIP_RTOL = 1e-4
MULTICHIP_STEPS = 5
MULTICHIP_BATCH = 64

# the real widths: bert-mlm-120m training at seq 512 / batch 32
REAL = ["--arch", "bert-mlm-120m", "--seq", "512", "--batch", "32"]


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def launch(argv):
    """One run of the training launcher; returns ``(state, TrainerLog)``."""
    from repro.launch.train import main as train_main

    print(f"[smoke] launch {' '.join(argv)}", flush=True)
    return train_main(list(argv))


def losses_by_step(log) -> dict:
    return {s: m["loss"] for s, m in zip(log.steps, log.metrics)}


def state_devices(state) -> set:
    return {d for leaf in jax.tree_util.tree_leaves(state)
            for d in leaf.devices()}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def train_argv(workdir: Path, base, *extra):
    return [*base, "--steps", str(TRAIN_STEPS), "--log-every", "1",
            "--data-dir", str(workdir / "data"),
            "--ckpt-dir", str(workdir / "ckpt"),
            "--ckpt-every", str(RESUME_FROM), "--devices", "1", *extra]


def phase_train(workdir: Path, base=REAL) -> dict:
    """Train from scratch; returns the per-step losses and telemetry."""
    shutil.rmtree(workdir, ignore_errors=True)
    state, log = launch(train_argv(workdir, base))
    losses = losses_by_step(log)
    check(sorted(losses) == list(range(1, TRAIN_STEPS + 1)),
          f"logged steps {sorted(losses)}")
    check(all(math.isfinite(v) for v in losses.values()),
          f"non-finite loss: {losses}")
    check(losses[TRAIN_STEPS] < losses[1],
          f"loss did not fall: {losses[1]} -> {losses[TRAIN_STEPS]}")
    check(log.telemetry["n_traces"] == 1,
          f"compiles={log.telemetry['n_traces']}, want 1")
    on = state_devices(state)
    check(on == {jax.devices()[0]}, f"state lives on {on}")
    t = log.telemetry
    print(f"[smoke] train: loss {losses[1]:.4f} -> "
          f"{losses[TRAIN_STEPS]:.4f}, compiles={t['n_traces']:.0f}, "
          f"state on {sorted(str(d) for d in on)}, "
          f"step_time_ema={t['step_time_ema'] * 1e3:.3f}ms "
          f"tokens/s={t['tokens_per_s']:.1f}", flush=True)
    return losses


def phase_resume(workdir: Path, first: dict, base=REAL) -> float:
    """Resume from the step-10 checkpoint; returns the largest relative
    loss difference against the uninterrupted run."""
    _, log = launch(train_argv(workdir, base, "--resume", "--ckpt-step",
                               str(RESUME_FROM)))
    resumed = losses_by_step(log)
    want = list(range(RESUME_FROM + 1, TRAIN_STEPS + 1))
    check(sorted(resumed) == want, f"resumed steps {sorted(resumed)}")
    rel = max(abs(resumed[s] - first[s]) / abs(first[s]) for s in want)
    exact = all(resumed[s] == first[s] for s in want)
    print(f"[smoke] resume: steps {want[0]}-{want[-1]} bit-exact={exact} "
          f"max_rel_diff={rel:.3e}", flush=True)
    check(rel <= RESUME_RTOL, f"resumed losses differ by {rel:.3e}")
    return rel


def kernel_cases(small: bool = False):
    """(name, kernel thunk, oracle thunk) at real widths, or at a few
    tiles' worth when ``small`` (the CPU rehearsal)."""
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.kernels.fused_xent import fused_xent
    from repro.kernels.paged_attention import paged_attention_fwd
    from repro.kernels.ssd_scan import ssd_scan

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))
    bf = jnp.bfloat16

    def normal(shape, dtype=bf):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    # flash attention at bert-mlm-120m widths (encoder: not causal)
    B, S, H, D = (2, 256, 4, 64) if small else (8, 512, 12, 64)
    q, k, v = normal((B, S, H, D)), normal((B, S, H, D)), normal((B, S, H, D))
    yield ("flash_attention",
           lambda: flash_attention_fwd(q, k, v, causal=False),
           lambda: kref.flash_attention_ref(*_f32(q, k, v), causal=False))

    # fused cross-entropy at the bert-mlm-120m vocabulary
    T, V = (256, 1024) if small else (4096, 32768)
    logits = normal((T, V))
    labels = jax.random.randint(next(keys), (T,), 0, V)
    yield ("fused_xent", lambda: fused_xent(logits, labels),
           lambda: kref.xent_ref(logits.astype(jnp.float32), labels))

    # SSD scan at mamba2-130m widths: 24 heads of 64, state 128, chunk 256
    Bb, S, H, P, N, L = (1, 256, 2, 64, 128, 128) if small else \
        (2, 2048, 24, 64, 128, 256)
    x = normal((Bb, S, H, P))
    dt = jax.nn.softplus(normal((Bb, S, H), jnp.float32) - 3.0)
    A = -jnp.exp(jax.random.uniform(next(keys), (H,), jnp.float32, 0.0,
                                    math.log(16.0)))
    Bm, Cm = normal((Bb, S, 1, N)), normal((Bb, S, 1, N))
    yield ("ssd_scan", lambda: ssd_scan(x, dt, A, Bm, Cm, L),
           lambda: kref.ssd_ref(*_f32(x, dt, A, Bm, Cm), chunk=L))

    # paged decode at starcoder2-3b widths: 24 heads, 2 kv heads of 128,
    # 16-token pages
    B, H, Hkv, D, P, maxp = (2, 4, 2, 128, 16, 4) if small else \
        (8, 24, 2, 128, 16, 64)
    n_pages = 1 + B * maxp
    qd = normal((B, H, D))
    kp, vp = normal((n_pages, P, Hkv, D)), normal((n_pages, P, Hkv, D))
    tables = (1 + jax.random.permutation(next(keys), B * maxp)).reshape(
        B, maxp).astype(jnp.int32)
    lens = jax.random.randint(next(keys), (B,), 0, maxp * P)
    yield ("paged_attention",
           lambda: paged_attention_fwd(qd, kp, vp, tables, lens),
           lambda: kref.paged_attention_ref(*_f32(qd, kp, vp), tables, lens))


def _f32(*xs):
    return [x.astype(jnp.float32) for x in xs]


def phase_kernels(small: bool = False) -> dict:
    """Each kernel on the default backend (compiled on a TPU) against its
    f32 oracle; returns the error of each relative to BF16_TOL's bound."""
    out = {}
    for name, run, oracle in kernel_cases(small):
        got = jax.tree_util.tree_leaves(run())
        with jax.default_matmul_precision("highest"):
            want = jax.tree_util.tree_leaves(oracle())
        err = 0.0
        for g, w in zip(got, want):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            check(g.shape == w.shape, f"{name}: shape {g.shape} != {w.shape}")
            check(np.isfinite(g).all(), f"{name}: non-finite output")
            bound = BF16_TOL * max(1.0, float(np.abs(w).max()))
            err = max(err, float(np.abs(g - w).max()) / bound)
        print(f"[smoke] kernel {name}: max_err/bound={err:.4f}", flush=True)
        check(err <= 1.0, f"{name}: error {err:.3f}x the bf16 bound")
        out[name] = err
    return out


def phase_multichip(workdir: Path, base=REAL, n_chips: int = 4) -> dict:
    """ddp and fsdp on ``n_chips`` against one device: same global batch,
    same seed, same data.  Returns the largest relative loss difference
    of each against the one-device run."""
    devices = jax.devices()
    check(len(devices) >= n_chips, f"{len(devices)} devices < {n_chips}")
    shutil.rmtree(workdir, ignore_errors=True)
    # a fixed loader and a smaller corpus (over 8 batches of 64 at seq 512):
    # auto-tuning and the full corpus are the train phase's to check
    argv = [*base, "--batch", str(MULTICHIP_BATCH), "--steps",
            str(MULTICHIP_STEPS), "--log-every", "1", "--workers", "1",
            "--n-functions", "1000", "--data-dir", str(workdir / "data")]
    runs = {}
    with jax.default_matmul_precision("highest"):
        for sharding in ("ddp", "fsdp"):
            state, log = launch(argv + ["--sharding", sharding,
                                        "--devices", str(n_chips)])
            check(log.telemetry["grad_sync"] == {
                "ddp": "bucketed_overlap",
                "fsdp": "scatter_overlap"}[sharding],
                f"{sharding}: grad_sync={log.telemetry['grad_sync']}")
            _check_layout(state["params"], sharding, set(devices[:n_chips]))
            runs[sharding] = log
            del state
        state, ref = launch(argv + ["--devices", "1"])
    check(state_devices(state) == {devices[0]}, "reference left device 0")
    del state
    want = losses_by_step(ref)
    check(sorted(want) == list(range(1, MULTICHIP_STEPS + 1)),
          f"reference steps {sorted(want)}")
    out, failed = {}, []
    for sharding, log in runs.items():
        got = losses_by_step(log)
        check(sorted(got) == sorted(want), f"{sharding} steps differ")
        step1 = max(abs(log.metrics[0][k] - ref.metrics[0][k])
                    / abs(ref.metrics[0][k]) for k in ("loss", "grad_norm"))
        rel = max(abs(got[s] - want[s]) / abs(want[s]) for s in want)
        print(f"[smoke] {sharding} x{n_chips} vs 1 device: "
              f"losses {[got[s] for s in sorted(want)]} vs "
              f"{[want[s] for s in sorted(want)]}; step-1 loss/grad_norm "
              f"rel_diff={step1:.3e}, max_rel_diff={rel:.3e}", flush=True)
        if not (all(map(math.isfinite, got.values()))
                and step1 <= STEP1_RTOL and rel <= MULTICHIP_RTOL):
            failed.append(sharding)
        out[sharding] = rel
    check(not failed, f"{failed} disagree with one device")
    return out


def _check_layout(params, sharding: str, devices: set):
    """Every param spans the whole mesh; ddp replicates each one, fsdp
    stores about a 1/n shard of the params on each device (ZeRO-3)."""
    leaves = jax.tree_util.tree_leaves(params)
    total = sum(leaf.nbytes for leaf in leaves)
    first = next(iter(devices))
    here = 0
    for leaf in leaves:
        check(leaf.sharding.device_set == devices,
              f"{sharding}: a param spans {leaf.sharding.device_set}")
        here += sum(s.data.nbytes for s in leaf.addressable_shards
                    if s.device == first)
    share = here / total
    print(f"[smoke] {sharding}: params on {len(devices)} devices, "
          f"{share:.3f} of the param bytes on {first}", flush=True)
    if sharding == "ddp":
        check(share == 1.0, f"ddp holds {share:.3f} of the params")
    else:
        check(share < 1.5 / len(devices),
              f"fsdp holds {share:.3f} of the params per device")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def run_phase(name: str, fn, *args):
    t0 = time.perf_counter()
    try:
        out = fn(*args)
    except Exception:  # report the phase and keep the others running
        traceback.print_exc()
        print(f"[smoke] phase {name}: FAIL "
              f"({time.perf_counter() - t0:.1f}s)", flush=True)
        return False, None
    print(f"[smoke] phase {name}: PASS ({time.perf_counter() - t0:.1f}s)",
          flush=True)
    return True, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the ddp/fsdp vs one-device phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    print(f"[smoke] device kind={dev.device_kind} platform={dev.platform} "
          f"visible={jax.device_count()} using={args.chips}", flush=True)
    from repro.launch.compile_cache import init_compile_cache

    print(f"[smoke] compile cache: {init_compile_cache()}", flush=True)

    if args.chips > 1:
        results = [run_phase("multichip", phase_multichip,
                             RUN_DIR / "multichip", REAL, args.chips)[0]]
    else:
        ok_train, first = run_phase("train", phase_train, RUN_DIR / "train")
        results = [ok_train]
        if ok_train:
            results.append(run_phase("resume", phase_resume,
                                     RUN_DIR / "train", first)[0])
        else:
            print("[smoke] phase resume: FAIL (no first run)", flush=True)
            results.append(False)
        results.append(run_phase("kernels", phase_kernels)[0])
    if not all(results):
        print("[smoke] FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
