"""From a profiler trace to the intervals the per-layer metrics read.

``load(path)`` reads a ``.xplane.pb`` with ``jax.profiler.ProfileData``
and keeps three kinds of events, all on the trace's one clock (ns):

* device ops: the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane, by
  HLO instruction name (a Pallas kernel's is the ``name`` it was given),
  and the ``Async XLA Ops`` line, which holds each async op (a collective,
  a copy) from its start to its done;
* host spans: the benchmark's own ``TraceAnnotation`` spans (``bench.*``)
  on the host planes.

The reductions below are what every metric shares: the union of the
intervals in which ops do work (a loop that holds other ops is left out:
it would cover its body's gaps), idle share, summed kernel time, and the time in which a
collective runs with no compute beside it.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

COLLECTIVE = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
              "all-to-all")
# ops that hold other ops (a scan's loop): busy, but no work of their own
CONTAINER = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    device: int
    name: str      # the HLO instruction's name, e.g. "gossip_mix_nodes.11"
    start: float   # ns
    dur: float     # ns
    in_flight: bool = False   # an async op's span from start to done

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def kind(self) -> str:
        """The name without its instance number: "fusion", "all-gather-start"."""
        return re.sub(r"[.\d]+$", "", self.name)


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Tuple[str, float, float]]   # (name, start ns, end ns)

    def devices(self) -> List[int]:
        return sorted({o.device for o in self.ops})

    def on(self, device: int, in_flight: bool = False) -> List[Op]:
        return [o for o in self.ops if o.device == device and o.in_flight == in_flight]

    def to_json(self) -> Dict:
        return {"ops": [dataclasses.astuple(o) for o in self.ops],
                "spans": [list(s) for s in self.spans]}

    @staticmethod
    def from_json(d: Dict) -> "Trace":
        return Trace([Op(*o) for o in d["ops"]], [tuple(s) for s in d["spans"]])


def _device_index(plane_name: str):
    tail = plane_name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else None


def _short(hlo: str) -> str:
    """``"%fusion.12 = f32[...] fusion(...)"`` -> ``"fusion.12"``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = _device_index(plane.name)
            for line in plane.lines:
                if line.name not in ("XLA Ops", "Async XLA Ops"):
                    continue
                flight = line.name == "Async XLA Ops"
                for e in line.events:
                    ops.append(Op(dev, _short(e.name), float(e.start_ns),
                                  float(e.duration_ns), flight))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
    return Trace(ops, spans)


def find_xplane(directory: Path) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def save_json_gz(trace: Trace, path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace.to_json(), f)


def load_json_gz(path: Path) -> Trace:
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))


# -- reductions ---------------------------------------------------------------


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Tuple[float, float]], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]):
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    b = list(b)
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy(ops: Iterable[Op], lo: float, hi: float):
    return union(clip(((o.start, o.end) for o in ops), lo, hi))


def work(trace: Trace, device: int) -> List[Op]:
    """The ops of ``device`` that do work: neither an async op's flight
    nor a loop that holds other ops (which would cover its body's gaps)."""
    return [o for o in trace.on(device) if not is_container(o)]


def window(trace: Trace) -> Tuple[float, float]:
    """The traced window: from the first to the end of the last benchmark
    span (the run_span calls and the final block)."""
    if not trace.spans:
        raise ValueError("the trace holds no bench.* host span")
    return min(s for _, s, _ in trace.spans), max(e for _, _, e in trace.spans)


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an op that does work ran, averaged over the devices."""
    lo, hi = window(trace)
    devs = trace.devices()
    return sum(length(busy(work(trace, d), lo, hi)) for d in devs) / max(len(devs), 1) / 1e9


def idle_share(trace: Trace) -> float:
    lo, hi = window(trace)
    return 1.0 - busy_seconds(trace) * 1e9 / (hi - lo)


def is_collective(op: Op) -> bool:
    return op.kind.startswith(COLLECTIVE)


def is_container(op: Op) -> bool:
    return op.kind in CONTAINER


def kernel_ops(trace: Trace, kernel: str) -> List[Op]:
    """Calls of the kernel named ``kernel`` inside the window."""
    lo, hi = window(trace)
    return [o for o in trace.ops if not o.in_flight and o.kind == kernel
            and o.start >= lo and o.end <= hi]


def exposed_collective_share(trace: Trace):
    """Share of the window in which a collective runs on a device (an
    async one from its start to its done) and no other op does, averaged
    over the devices; None without collectives."""
    lo, hi = window(trace)
    shares, seen = [], False
    for d in trace.devices():
        ops = [o for o in trace.ops if o.device == d]
        coll = busy([o for o in ops if is_collective(o)], lo, hi)
        seen |= bool(coll)
        comp = busy([o for o in work(trace, d) if not is_collective(o)], lo, hi)
        shares.append(length(subtract(coll, comp)) / (hi - lo))
    return sum(shares) / len(shares) if seen else None


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """[kind, seconds] of the kinds of op that took the most device time
    in the window, summed over their calls and averaged over the devices;
    loops that hold other ops are left out."""
    lo, hi = window(trace)
    tot: Dict[str, float] = {}
    for o in trace.ops:
        if not o.in_flight and not is_container(o) and o.start >= lo and o.end <= hi:
            tot[o.kind] = tot.get(o.kind, 0.0) + o.dur
    nd = max(len(trace.devices()), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd / 1e9] for k, v in best]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """[host span, seconds] of the longest idle gaps of device 0, each
    named by the innermost benchmark span over the gap's midpoint."""
    lo, hi = window(trace)
    dev = trace.devices()[0]
    gaps = subtract([(lo, hi)], busy(work(trace, dev), lo, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        over = [sp for sp in trace.spans if sp[1] <= mid <= sp[2]]
        name = min(over, key=lambda sp: sp[2] - sp[1])[0] if over else "outside bench spans"
        out.append([name, (e - s) / 1e9])
    return out
