"""Trim a profile recorded on the chip to one step of one chip, small
enough to keep with the tests:

    python bench/tests/make_trace_fixture.py <saved.json.gz> <out.json.gz>
    python bench/tests/make_trace_fixture.py --xplane <file.xplane.pb> <out>

The first form reads what ``bench/run.py --trace 1 --keep-trace <file>``
saved.  Its window becomes the second whole step of the step program in
the traced window, from the middle of the gap before it to the middle of
the gap after it.  Device events and host spans are kept where they
overlap that window, and the compiled text keeps the computations'
headers, the instructions those events name, and every fusion,
convolution and dot of the computations they call (what ``hlo_index``
reads).

The second form reads a bare profile of a jitted step, with no host
spans and no compiled text.  Its window is the step program's second
run, from the middle of the gap before it to a millisecond after it, and
the compiled text is made of the instructions the events themselves
spell out, in one computation.
"""
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace as tr  # noqa: E402


def trim_hlo(hlo: str, names) -> str:
    comps, order, cur = {}, [], None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = head.group(1)
            comps[cur] = [line]
            order.append(cur)
        elif cur is not None:
            comps[cur].append(line)
    instr = {}
    for comp, lines in comps.items():
        for i, line in enumerate(lines):
            m = tr._INSTR.match(line)
            if m:
                instr[m.group(1)] = (comp, i, m.group(2))
    keep = {c: set() for c in comps}
    todo = [n for n in names if n in instr]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        comp, i, _ = instr[name]
        keep[comp].add(i)
        line = comps[comp][i]
        for callee in re.findall(r"(?:calls|to_apply|body|condition)="
                                 r"%([\w.\-]+)", line):
            for j, inner in enumerate(comps.get(callee, [])):
                m = tr._INSTR.match(inner)
                if m and m.group(2) in ("fusion", "convolution", "dot"):
                    todo.append(m.group(1))
    out = [hlo.splitlines()[0]]
    for comp in order:
        if keep[comp]:
            out.append(comps[comp][0])
            out += [comps[comp][i] for i in sorted(keep[comp])]
            out.append("}")
    return "\n".join(out) + "\n"


def main(src: str, dst: str):
    profile, spans, hlo = tr.load_saved(Path(src))
    module = re.match(r"HloModule ([^\s,]+)", hlo).group(1)
    chip = sorted(profile["chips"])[0]
    lines = profile["chips"][chip]
    w0, w1 = profile["window"]
    steps = tr.union([(a, b) for n, a, b in lines["XLA Modules"]
                      if n.split("(")[0] == module and w0 <= a and b <= w1])
    (_, e0), (s1, e1), (s2, _) = steps[0], steps[1], steps[2]
    lo, hi = (e0 + s1) // 2, (e1 + s2) // 2
    cut = {ln: [ev for ev in evs if ev[2] > lo and ev[1] < hi]
           for ln, evs in lines.items()}
    w_host = next(s for s in spans if s[0] == tr.WINDOW)
    h0 = w_host[2] + (lo - w0) / 1e3
    kept = [s for s in spans if s[0] != tr.WINDOW
            and s[3] > h0 and s[2] < h0 + (hi - lo) / 1e3]
    names = {ev[0] for evs in cut.values() for ev in evs}
    tr.save(Path(dst), {"window": [lo, hi], "chips": {chip: cut}},
            [[tr.WINDOW, w_host[1], h0, h0 + (hi - lo) / 1e3]] + kept,
            trim_hlo(hlo, names))


def from_xplane(src: str, dst: str):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(src)
    plane = next(p for p in pd.planes if p.name.startswith("/device:TPU:"))
    lines = {ln.name: [[e.name, int(e.start_ns), int(e.end_ns)]
                       for e in ln.events]
             for ln in plane.lines if ln.name in tr.DEVICE_LINES}
    (_, _, e0), (module, s1, e1) = lines["XLA Modules"][:2]
    lo, hi = (e0 + s1) // 2, e1 + 1_000_000
    instrs = {}
    for ev in lines["XLA Ops"] + lines["Async XLA Ops"]:
        instrs.setdefault(tr._short(ev[0]), ev[0])
    cut = {ln: [[tr._short(n), a, b] for n, a, b in evs if b > lo and a < hi]
           for ln, evs in lines.items()}
    hlo = (f"HloModule {module.split('(')[0]}\n\nENTRY %main {{\n  "
           + "\n  ".join(instrs.values()) + "\n}\n")
    tr.save(Path(dst), {"window": [lo, hi], "chips": {plane.name: cut}},
            [[tr.WINDOW, "bench", lo / 1e3, hi / 1e3]], hlo)


if __name__ == "__main__":
    if sys.argv[1] == "--xplane":
        from_xplane(*sys.argv[2:4])
    else:
        main(*sys.argv[1:3])
