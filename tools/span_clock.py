"""How far the program's host spans sit from their own profiler events.

While a ``repro.observability.Tracer`` is installed, every span it
records is also a ``jax.profiler.TraceAnnotation`` of the same name, so
under a profiler session the span lands on the profile's host plane,
on the clock of the device's operations.  This pairs each span of a
tracer's Chrome-trace file with its event on the host plane and prints,
per span name, the pairs found, the spread of the offset between the
two clocks (profiler start minus tracer start, after the offset of all
pairs) and the largest difference of the two durations:

    python tools/span_clock.py <profile dir or .xplane.pb> <trace-P.json>

A span recorded with ``Tracer.complete`` (no annotation) pairs only
with an annotation of its own name that the caller entered: the
benchmark's ``bench_window`` anchor is such a pair, and its line shows
how far that anchor sits from the mirrored spans.
"""
from __future__ import annotations

import collections
import glob
import json
import os
import statistics
import sys
from typing import Dict, Iterable, List, Sequence, Tuple

# (start, duration), both in microseconds
Interval = Tuple[float, float]


def host_events(profile: str, names: Iterable[str]
                ) -> Dict[str, List[Interval]]:
    """Events called one of ``names`` on the host planes of the newest
    profile under ``profile`` (a directory or an ``.xplane.pb``)."""
    from jax.profiler import ProfileData

    path = profile
    if os.path.isdir(profile):
        path = sorted(glob.glob(os.path.join(profile, "**", "*.xplane.pb"),
                                recursive=True))[-1]
    names = set(names)
    out: Dict[str, List[Interval]] = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out[ev.name].append((ev.start_ns / 1e3,
                                         ev.duration_ns / 1e3))
    return out


def tracer_spans(events: Sequence[dict]) -> Dict[str, List[Interval]]:
    """The complete spans of a tracer's Chrome-trace events, by name."""
    out: Dict[str, List[Interval]] = collections.defaultdict(list)
    for e in events:
        if e.get("ph") == "X":
            out[e["name"]].append((e["ts"], e["dur"]))
    return out


def clock_offset(host: Dict[str, List[Interval]],
                 spans: Dict[str, List[Interval]], bin_us: float = 20.0
                 ) -> float:
    """The offset (host start minus span start) shared by most pairs of
    one name: the mode over every same-name pair, in ``bin_us`` bins,
    refined to the median of the pairs in that bin."""
    offsets = [h - s for name, hs in host.items()
               for h, _ in hs for s, _ in spans.get(name, ())]
    if not offsets:
        raise ValueError("no span has a host event of its name")
    bins = collections.Counter(round(o / bin_us) for o in offsets)
    top = bins.most_common(1)[0][0]
    return statistics.median(o for o in offsets
                             if round(o / bin_us) == top)


def pair(host: Dict[str, List[Interval]],
         spans: Dict[str, List[Interval]], tolerance_us: float = 1000.0
         ) -> Dict[str, List[Tuple[float, float]]]:
    """For each host event, the span of its name whose start lies
    nearest the shared offset (within ``tolerance_us``), each span used
    once: name -> [(offset, host duration - span duration)] in µs."""
    c = clock_offset(host, spans)
    out: Dict[str, List[Tuple[float, float]]] = {}
    for name, hs in host.items():
        free = sorted(spans.get(name, ()))
        got = []
        for h, hd in sorted(hs):
            best = min(range(len(free)), default=None,
                       key=lambda i: abs(h - free[i][0] - c))
            if best is None or abs(h - free[best][0] - c) > tolerance_us:
                continue
            s, sd = free.pop(best)
            got.append((h - s, hd - sd))
        out[name] = got
    return out


def report(pairs: Dict[str, List[Tuple[float, float]]]
           ) -> Dict[str, Dict[str, float]]:
    """Per name: pairs, offset spread (max - min) and its median's
    distance from the offset of all pairs, largest duration gap; under
    ``*`` the same over every name."""
    c = statistics.median(o for got in pairs.values() for o, _ in got)
    rows = {}
    for name, got in sorted(pairs.items()) + [
            ("*", [p for got in pairs.values() for p in got])]:
        if not got:
            continue
        offs = [o for o, _ in got]
        rows[name] = {"pairs": len(got),
                      "offset_spread_us": max(offs) - min(offs),
                      "offset_from_all_us": statistics.median(offs) - c,
                      "max_duration_gap_us": max(abs(d) for _, d in got)}
    return rows


def main(argv=None) -> int:
    profile, trace = (argv or sys.argv[1:])[:2]
    with open(trace) as f:
        doc = json.load(f)
    spans = tracer_spans(doc.get("traceEvents", doc))
    rows = report(pair(host_events(profile, spans), spans))
    for name, r in rows.items():
        print(f"{name:20s} {r['pairs']:6d} pairs  spread "
              f"{r['offset_spread_us']:9.3f} us  from all "
              f"{r['offset_from_all_us']:9.3f} us  duration gap "
              f"{r['max_duration_gap_us']:9.3f} us")
    return 0


if __name__ == "__main__":
    sys.exit(main())
