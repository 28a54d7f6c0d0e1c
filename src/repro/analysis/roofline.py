"""Roofline terms from a compiled dry-run artifact.

  compute term    = HLO_FLOPs_global   / (chips × peak_FLOP/s)
  memory term     = HLO_bytes_global   / (chips × HBM_bw)
  collective term = collective_bytes_global / (chips × link_bw)

``cost_analysis()`` on the SPMD-partitioned executable reports PER-DEVICE
flops/bytes (verified empirically — see EXPERIMENTS.md §Dry-run), so global
terms are per_device × chips and the division by chips cancels: each term
is simply per-device work over per-chip bandwidth — i.e. seconds for the
slowest chip, which is what a roofline wants.

collective_bytes is not in cost_analysis: we parse the optimized HLO,
build a symbol table of instruction result shapes, and sum the *operand*
bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute.
"""
from __future__ import annotations

import dataclasses
import json
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from repro.core.scaling import TPU_V5E

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# "%name = f32[128,512]{1,0} op-name(...)" (also tuple results)
_DEF_RE = re.compile(r"%([\w.\-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[\d,]*\][^ ]*)\s+([\w\-]+)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device operand bytes of each collective kind in the program."""
    table: Dict[str, int] = {}
    lines = hlo_text.splitlines()
    for ln in lines:
        m = _DEF_RE.search(ln)
        if m:
            table[m.group(1)] = _shape_bytes(m.group(2))
    out = {k: 0.0 for k in _COLLECTIVES}
    out["n_ops"] = 0.0
    for ln in lines:
        m = _DEF_RE.search(ln)
        if not m:
            continue
        kind = m.group(3)
        # strip variants like all-reduce-start / all-gather-done
        base = None
        for c in _COLLECTIVES:
            if kind == c or kind.startswith(c + "-start"):
                base = c
                break
        if base is None:
            continue
        paren = ln[ln.index("(") + 1:] if "(" in ln else ""
        depth = 1
        args = ""
        for ch in paren:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
            args += ch
        ops = _OPERAND_RE.findall(args)
        out[base] += float(sum(table.get(o, 0) for o in ops))
        out["n_ops"] += 1
    out["total"] = sum(v for k, v in out.items()
                       if k in _COLLECTIVES)
    return out


# ---------------------------------------------------------------------------


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    sharding: str
    # per-device quantities (slowest-chip view)
    flops_per_device: float
    hbm_bytes_per_device: float
    coll_bytes_per_device: float
    coll_breakdown: Dict[str, float]
    # memory fit
    arg_bytes: float
    temp_bytes: float
    out_bytes: float
    # analytic
    model_flops_global: float
    # raw XLA numbers (while bodies counted once — reference only)
    xla_cost: Dict[str, float] = field(default_factory=dict)
    # hardware
    peak_flops: float = TPU_V5E.peak_flops
    hbm_bw: float = 819e9
    link_bw: float = 50e9
    hbm_cap: float = 16e9

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes_per_device / self.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_device / self.link_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        g = self.flops_per_device * self.chips
        return self.model_flops_global / g if g else float("nan")

    @property
    def temp_bytes_tpu_est(self) -> float:
        """XLA:CPU promotes bf16 compute to f32 (verified in the buffer
        dump — every large temp is f32 where the TPU program is bf16), so
        the CPU temp arena overstates the TPU footprint by ~2x for bf16
        programs.  This halves the temp as the TPU estimate; the raw CPU
        number is kept in ``temp_bytes``.  Where indexed (int32) or f32
        state dominates this is conservative in the other direction."""
        return self.temp_bytes * 0.5

    @property
    def fits_hbm(self) -> bool:
        return (self.arg_bytes + self.temp_bytes_tpu_est + self.out_bytes) \
            <= self.hbm_cap

    @property
    def fits_hbm_raw(self) -> bool:
        return (self.arg_bytes + self.temp_bytes + self.out_bytes) \
            <= self.hbm_cap

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(
            t_compute=self.t_compute, t_memory=self.t_memory,
            t_collective=self.t_collective, dominant=self.dominant,
            useful_flops_ratio=self.useful_flops_ratio,
            fits_hbm=self.fits_hbm, fits_hbm_raw=self.fits_hbm_raw,
            temp_bytes_tpu_est=self.temp_bytes_tpu_est,
            bound_time=self.bound_time,
        )
        return d


def analyze(compiled, *, arch: str, shape: str, mesh_name: str, chips: int,
            sharding: str, model_flops_global: float,
            hlo_text: Optional[str] = None, pallas_cost=None) -> Roofline:
    """Roofline terms from the compiled artifact.

    flops/bytes/collectives come from the trip-count-aware HLO cost model
    (``analysis.hlocost``) because XLA's ``cost_analysis()`` counts scan
    (while) bodies once — see hlocost.py.  The raw XLA numbers are kept in
    ``xla_cost`` for reference.
    """
    from repro.analysis.hlocost import analyze_text

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    txt = hlo_text if hlo_text is not None else compiled.as_text()
    cost = analyze_text(txt, pallas_cost)
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        sharding=sharding,
        flops_per_device=cost.flops,
        hbm_bytes_per_device=cost.bytes,
        coll_bytes_per_device=cost.coll_total,
        coll_breakdown={k: cost.coll.get(k, 0.0) for k in _COLLECTIVES},
        arg_bytes=float(getattr(ma, "argument_size_in_bytes", 0)),
        temp_bytes=float(getattr(ma, "temp_size_in_bytes", 0)),
        out_bytes=float(getattr(ma, "output_size_in_bytes", 0)),
        model_flops_global=model_flops_global,
        xla_cost={"flops": float(ca.get("flops", 0.0)),
                  "bytes_accessed": float(ca.get("bytes accessed", 0.0))},
    )


def save_records(path: str, records: List[Roofline]):
    import os

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump([r.to_dict() for r in records], f, indent=1)


def markdown_table(records: List[dict]) -> str:
    hdr = ("| arch | shape | mesh | sharding | t_compute | t_memory | "
           "t_collective | dominant | useful/HLO | fits HBM |\n"
           "|---|---|---|---|---|---|---|---|---|---|")
    rows = []
    for r in records:
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {r['sharding']} "
            f"| {r['t_compute']*1e3:.2f} ms | {r['t_memory']*1e3:.2f} ms "
            f"| {r['t_collective']*1e3:.2f} ms | **{r['dominant']}** "
            f"| {r['useful_flops_ratio']:.2f} | "
            f"{'yes' if r['fits_hbm'] else 'NO'} |")
    return "\n".join([hdr] + rows)
