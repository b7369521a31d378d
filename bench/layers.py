"""Per-layer attribution of a traced run: which program, which named scope
and which of the program's own host spans each moment of device time
belongs to.

``traces.load`` keeps the device ops by HLO name and the benchmark's
``bench.*`` spans.  ``load`` here reads the same ``.xplane.pb`` for three
things more, all on the trace's one clock (ns):

* each device op's program: the ``XLA Modules`` interval of its device
  that holds it, by module name (``jit__chunk_fn``, ``jit_stage_batches``);
* each op's scope, one of ``SCOPES`` (the named scopes of
  ``core/steps.py``): from the ``op_name`` metadata of its instruction in
  that program's compiled HLO, which the profiler keeps in its
  ``/host:metadata`` plane.  A scope counts as a word anywhere in the name
  stack, inside ``jvp(...)`` or ``transpose(...)`` too; the innermost
  wins.  An instruction the compiler made without metadata (a layout
  copy, a fusion of cloned instructions) takes the scope most common
  among the instructions it fuses, else that of its first scoped operand;
* the program's host spans ``dl.*`` (``repro/utils/spans.py``) with their
  stats (``rnd``, ``bytes``) and their parent by nesting on one thread.

A run of a program without scopes, ``dl.*`` spans or ``jit_stage_batches``
loads with those empty, and every reader below returns None.  The window
is ``traces.window``'s: the ``bench.*`` spans alone.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import gzip
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import traces

SCOPES = ("local_step", "flatten", "share_mix", "unflatten")
CHUNK = "jit__chunk_fn"
STAGE = "jit_stage_batches"
TRACE_DIR = Path(__file__).resolve().parent.parent / ".bench_trace"
NO_SPAN = "no span"


@dataclasses.dataclass
class LayerOp(traces.Op):
    program: str = ""   # module name without its fingerprint
    scope: str = ""     # one of SCOPES, or "" where none claims it


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    stats: Dict = dataclasses.field(default_factory=dict)
    parent: int = -1    # index of the innermost enclosing dl.* span


@dataclasses.dataclass
class Layers(traces.Trace):
    programs: List[Tuple[int, str, float, float]] = dataclasses.field(default_factory=list)
    host: List[Span] = dataclasses.field(default_factory=list)

    def to_json(self) -> Dict:
        progs = sorted({o.program for o in self.ops} | {p for _, p, _, _ in self.programs})
        scopes = ("",) + SCOPES
        return {"ops": [[o.device, o.name, o.start, o.dur, o.in_flight,
                         progs.index(o.program), scopes.index(o.scope)] for o in self.ops],
                "program_names": progs,
                "spans": [list(s) for s in self.spans],
                "programs": [list(p) for p in self.programs],
                "host": [dataclasses.astuple(s) for s in self.host]}

    @staticmethod
    def from_json(d: Dict) -> "Layers":
        progs, scopes = d["program_names"], ("",) + SCOPES
        ops = [LayerOp(dv, n, s, t, f, progs[p], scopes[c]) for dv, n, s, t, f, p, c in d["ops"]]
        return Layers(ops, [tuple(s) for s in d["spans"]],
                      [tuple(p) for p in d["programs"]], [Span(*h) for h in d["host"]])


# -- the compiled programs' scopes, from the profile's metadata plane ----------


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes):
    """(field number, value) of a protobuf message: ints for varints,
    bytes for length-delimited fields, skipped fixed-width ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield f, v


def _ints(v) -> List[int]:
    """A repeated int64 field's one element, or its packed list."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` named as a word in an op_name stack."""
    hits = [w for w in re.findall(r"[A-Za-z_]\w*", op_name) if w in SCOPES]
    return hits[-1] if hits else ""


def hlo_scopes(hlo_proto: bytes) -> Dict[str, str]:
    """{instruction name: scope} of a serialized ``xla.HloProto``."""
    instrs, comps = {}, {}   # id -> (name, op_name, operand ids, called ids)
    for f, module in _fields(hlo_proto):
        if f != 1:                       # HloProto.hlo_module
            continue
        for g, comp in _fields(module):
            if g != 3:                   # HloModuleProto.computations
                continue
            cid, members = None, []
            for h, v in _fields(comp):
                if h == 5:               # HloComputationProto.id
                    cid = v
                elif h == 2:             # .instructions
                    name, op_name, iid, operands, calls = "", "", None, [], []
                    for a, x in _fields(v):
                        if a == 1:
                            name = x.decode()
                        elif a == 7:     # .metadata -> OpMetadata.op_name
                            op_name = next((y.decode() for b, y in _fields(x) if b == 2), "")
                        elif a == 35:
                            iid = x
                        elif a == 36:
                            operands += _ints(x)
                        elif a == 38:
                            calls += _ints(x)
                    instrs[iid] = (name, op_name, operands, calls)
                    members.append(iid)
            comps[cid] = members
    memo: Dict[int, str] = {}

    def fused(cid, seen):
        votes = collections.Counter()
        for m in comps.get(cid, ()):
            _, op_name, _, calls = instrs[m]
            s = scope_of(op_name)
            if s:
                votes[s] += 1
            for c in calls:
                if c not in seen:
                    seen.add(c)
                    votes.update(fused(c, seen))
        return votes

    def resolve(iid, depth=0):
        if iid in memo:
            return memo[iid]
        name, op_name, operands, calls = instrs[iid]
        s = scope_of(op_name)
        if not s and calls:
            votes = collections.Counter()
            for c in calls:
                votes.update(fused(c, {c}))
            s = votes.most_common(1)[0][0] if votes else ""
        if not s and depth < 64:
            s = next((r for r in (resolve(o, depth + 1) for o in operands if o in instrs)
                      if r), "")
        memo[iid] = s
        return s

    return {instrs[i][0]: resolve(i) for i in instrs}


def module_scopes(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{module event name, e.g. "jit__chunk_fn(1830…)": {instruction: scope}}
    from the ``Hlo Proto`` stats of the profile's ``/host:metadata`` plane."""
    out = {}
    for f, plane in _fields(xspace):
        if f != 1:                                       # XSpace.planes
            continue
        fs = list(_fields(plane))
        if next((v for k, v in fs if k == 2), b"") != b"/host:metadata":
            continue
        stat_ids = set()
        for k, v in fs:                                  # XPlane.stat_metadata
            if k == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                if meta.get(2) == b"Hlo Proto":
                    stat_ids.add(meta.get(1, entry.get(1)))
        for k, v in fs:                                  # XPlane.event_metadata
            if k != 4:
                continue
            meta = list(_fields(dict(_fields(v)).get(2, b"")))
            name = next((x.decode() for a, x in meta if a == 2), "")
            for a, stat in meta:
                if a == 5:                               # XEventMetadata.stats
                    st = dict(_fields(stat))
                    if st.get(1) in stat_ids and 6 in st:
                        out[name] = hlo_scopes(st[6])
    return out


def module_name(event_name: str) -> str:
    """``"jit__chunk_fn(18309…)"`` -> ``"jit__chunk_fn"``."""
    return event_name.split("(", 1)[0]


# -- loading -------------------------------------------------------------------


def load(path) -> Layers:
    from jax.profiler import ProfileData

    path = Path(path)
    scopes = module_scopes(path.read_bytes())
    pd = ProfileData.from_file(str(path))
    ops, programs, bench, host = [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = traces._device_index(plane.name)
            lines = {line.name: line for line in plane.lines}
            mods = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
                          for e in (lines["XLA Modules"].events if "XLA Modules" in lines else ()))
            programs += [(dev, module_name(n), s, e) for s, e, n in mods]
            starts = [s for s, _, _ in mods]
            for lname in ("XLA Ops", "Async XLA Ops"):
                if lname not in lines:
                    continue
                for e in lines[lname].events:
                    start, name = float(e.start_ns), traces._short(e.name)
                    k = bisect.bisect_right(starts, start) - 1
                    full = mods[k][2] if k >= 0 and start < mods[k][1] else ""
                    ops.append(LayerOp(dev, name, start, float(e.duration_ns),
                                       lname == "Async XLA Ops", module_name(full),
                                       scopes.get(full, {}).get(name, "")))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                mine = []
                for e in line.events:
                    if e.name.startswith("bench."):
                        bench.append((e.name, float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
                    elif e.name.startswith("dl."):
                        mine.append(Span(e.name, float(e.start_ns),
                                         float(e.start_ns + e.duration_ns),
                                         {k: v for k, v in e.stats}))
                host += _nest(mine, offset=len(host))
    return Layers(ops, bench, programs, host)


def _nest(spans: List[Span], offset: int) -> List[Span]:
    """Spans of one thread in start order, each with its innermost parent."""
    spans = sorted(spans, key=lambda s: (s.start, -s.end))
    stack: List[int] = []
    for i, s in enumerate(spans):
        while stack and spans[stack[-1]].end < s.end:
            stack.pop()
        s.parent = offset + stack[-1] if stack else -1
        stack.append(i)
    return spans


@functools.lru_cache(maxsize=4)
def _load_cached(path: str, mtime_ns: int, size: int) -> Layers:
    return load(path)


def of(trace: traces.Trace, trace_dir: Optional[Path] = None) -> Optional[Layers]:
    """The ``Layers`` of a traced run: ``trace`` itself where it is one;
    else the profile under ``trace_dir`` (``TRACE_DIR``, where the
    benchmark writes its profiles), newest first, whose ``bench.*`` spans
    are ``trace``'s — the file ``traces.load`` read it from."""
    if isinstance(trace, Layers):
        return trace
    found = sorted(Path(trace_dir or TRACE_DIR).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns, reverse=True)
    for p in found:
        st = p.stat()
        lay = _load_cached(str(p), st.st_mtime_ns, st.st_size)
        if lay.spans == trace.spans:
            return lay
    return None


def save_json_gz(run: Layers, path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(run.to_json(), f)


def load_json_gz(path: Path) -> Layers:
    with gzip.open(path, "rt") as f:
        return Layers.from_json(json.load(f))


def trim(run: Layers, first: int, n: int = 2) -> Layers:
    """Chunks ``first`` .. ``first + n - 1`` of the window: their
    ``bench.run_span`` spans and what starts inside them."""
    kept = sorted(s for s in run.spans if s[0] == "bench.run_span")[first:first + n]
    lo, hi = kept[0][1], kept[-1][2]
    keep = [i for i, h in enumerate(run.host) if lo <= h.start and h.end <= hi]
    new = {old: i for i, old in enumerate(keep)}
    host = [dataclasses.replace(run.host[i], parent=new.get(run.host[i].parent, -1))
            for i in keep]
    return Layers([o for o in run.ops if lo <= o.start < hi], kept,
                  [p for p in run.programs if lo <= p[2] < hi], host)


# -- reductions ----------------------------------------------------------------


def chunks(run: Layers) -> int:
    """``dl.run_span`` calls inside the window."""
    lo, hi = traces.window(run)
    return sum(1 for h in run.host if h.name == "dl.run_span" and lo <= h.start < hi)


def _in_window(run: Layers):
    lo, hi = traces.window(run)
    return [o for o in run.ops if not o.in_flight and not traces.is_container(o)
            and o.start >= lo and o.end <= hi]


def scope_seconds(run: Layers, scopes: Sequence[str]) -> float:
    """Device seconds of the ops the scopes claim in the window, summed
    over their calls and averaged over the devices."""
    ops = [o for o in _in_window(run) if o.scope in scopes]
    return sum(o.dur for o in ops) / max(len(run.devices()), 1) / 1e9


def program_seconds(run: Layers, module: str) -> float:
    """Device seconds of the program ``module`` in the window (its
    ``XLA Modules`` intervals), averaged over the devices."""
    lo, hi = traces.window(run)
    ivs = [(s, e) for _, m, s, e in run.programs if m == module]
    return traces.length(traces.clip(ivs, lo, hi)) / max(len(run.devices()), 1) / 1e9


def span_seconds(run: Layers, name: str) -> float:
    """Host seconds in the spans ``name`` inside the window."""
    lo, hi = traces.window(run)
    return traces.length(traces.union(traces.clip(
        ((h.start, h.end) for h in run.host if h.name == name), lo, hi))) / 1e9


def idle(run: Layers, device: int) -> List[Tuple[float, float]]:
    """The device's idle intervals in the window, as ``traces.idle_share``
    counts them."""
    lo, hi = traces.window(run)
    return traces.subtract([(lo, hi)], traces.busy(traces.work(run, device), lo, hi))


def idle_under(run: Layers, name: str) -> Optional[float]:
    """Device-idle seconds under the spans ``name``, averaged over the
    devices; None for a trace without device ops."""
    under = traces.union((h.start, h.end) for h in run.host if h.name == name)
    devs = run.devices()
    if not devs:
        return None
    total = 0.0
    for d in devs:
        gaps = idle(run, d)
        total += traces.length(traces.subtract(gaps, traces.subtract(gaps, under)))
    return total / len(devs) / 1e9


def _innermost(run: Layers) -> List[Tuple[float, float, str]]:
    """The window cut at every span boundary, each piece named by the
    innermost span (``dl.*`` or ``bench.*``) over it."""
    spans = [(h.start, h.end, h.name) for h in run.host] + [(s, e, n) for n, s, e in run.spans]
    lo, hi = traces.window(run)
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e) if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        over = [sp for sp in spans if sp[0] <= a and b <= sp[1]]
        name = min(over, key=lambda sp: sp[1] - sp[0])[2] if over else NO_SPAN
        out.append((a, b, name))
    return out


def idle_split(run: Layers, device: Optional[int] = None) -> Dict[str, float]:
    """{innermost span: device-idle seconds under it} over the window."""
    device = run.devices()[0] if device is None else device
    pieces = _innermost(run)
    starts = [a for a, _, _ in pieces]
    out: Dict[str, float] = collections.defaultdict(float)
    for s, e in idle(run, device):
        k = max(bisect.bisect_right(starts, s) - 1, 0)
        while k < len(pieces) and pieces[k][0] < e:
            a, b, name = pieces[k]
            out[name] += max(0.0, min(b, e) - max(a, s)) / 1e9
            k += 1
    return dict(out)


def idle_gaps(run: Layers, n: int = 10) -> List[List]:
    """``traces.idle_gaps`` with each gap named by the innermost span of
    either family (``dl.*`` or ``bench.*``) over its midpoint."""
    dev = run.devices()[0]
    pieces = _innermost(run)
    starts = [a for a, _, _ in pieces]
    out = []
    for s, e in sorted(idle(run, dev), key=lambda g: g[0] - g[1])[:n]:
        k = bisect.bisect_right(starts, (s + e) / 2) - 1
        out.append([pieces[k][2] if k >= 0 else NO_SPAN, (e - s) / 1e9])
    return out


def claimed(run: Layers, module: str = CHUNK) -> Tuple[float, List[List]]:
    """(share of ``module``'s device time its ops' scopes claim, [kind,
    seconds] of the unclaimed ops' largest kinds)."""
    ops = [o for o in _in_window(run) if o.program == module]
    total = sum(o.dur for o in ops)
    rest = collections.Counter()
    for o in ops:
        if not o.scope:
            rest[o.kind] += o.dur / 1e9
    return (1.0 - sum(rest.values()) * 1e9 / total if total else 0.0,
            [[k, v] for k, v in rest.most_common(5)])


# -- what the metric readers share ---------------------------------------------


def scope_ms_per_round(trace, ctx, scopes: Sequence[str]):
    """Device ms a round of the ops ``scopes`` claim; None where none do."""
    run = of(trace)
    if run is None or not ctx["rounds"]:
        return None
    s = scope_seconds(run, scopes)
    return s * 1e3 / ctx["rounds"] if s else None


def per_chunk_ms(trace, seconds_of):
    """``seconds_of(run)`` in ms per ``dl.run_span`` of the window; None
    for a run without those spans, or where ``seconds_of`` gives None."""
    run = of(trace)
    n = chunks(run) if run is not None else 0
    s = seconds_of(run) if n else None
    return None if s is None else s * 1e3 / n
