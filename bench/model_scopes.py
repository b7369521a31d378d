"""Attribution of a traced run's device time to the model's own named
scopes inside the round's local step: ``attention`` and ``mlp`` of the
transformer block (``models/vit.py``; also its ``patch_embed`` and
``head``).

``layers`` attributes each device op to one of its fixed ``SCOPES``, the
round's layers.  This module reads the same compiled-HLO metadata of the
same profile for the words of ``MODEL_SCOPES`` instead, by the same rules:
a scope counts as a word anywhere in an instruction's ``op_name`` stack
(inside ``jvp(...)``, ``transpose(...)`` and a rematerialised
``checkpoint`` too), the innermost wins; an instruction whose op_name
names none of them takes the scope most common among the instructions it
fuses, else that of its first scoped operand.  The forward pass, its
recomputation in the backward pass and the backward pass all count.

Only ops that ``layers`` places in the round's ``local_step`` count: by
the operand rule the mixing's and the flat layout's ops, which read the
step's output, would otherwise fall to the last block's ``mlp``.

A run of a model without these scopes reads as None.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import gzip
import json
import re
from pathlib import Path
from typing import Dict, List, Optional

import layers
import traces

MODEL_SCOPES = ("patch_embed", "attention", "mlp", "head")
STEP = "local_step"   # the round layer (layers.SCOPES) the model runs in


def scope_of(op_name: str) -> str:
    """The innermost of ``MODEL_SCOPES`` named as a word in an op_name stack."""
    hits = [w for w in re.findall(r"[A-Za-z_]\w*", op_name) if w in MODEL_SCOPES]
    return hits[-1] if hits else ""


def hlo_scopes(hlo_proto: bytes) -> Dict[str, str]:
    """{instruction name: scope} of a serialized ``xla.HloProto``, by the
    rules of ``layers.hlo_scopes`` over ``MODEL_SCOPES``."""
    instrs, comps = {}, {}   # id -> (name, own scope, operand ids, called ids)
    for f, module in layers._fields(hlo_proto):
        if f != 1:                       # HloProto.hlo_module
            continue
        for g, comp in layers._fields(module):
            if g != 3:                   # HloModuleProto.computations
                continue
            cid, members = None, []
            for h, v in layers._fields(comp):
                if h == 5:               # HloComputationProto.id
                    cid = v
                elif h == 2:             # .instructions
                    name, op_name, iid, operands, calls = "", "", None, [], []
                    for a, x in layers._fields(v):
                        if a == 1:
                            name = x.decode()
                        elif a == 7:     # .metadata -> OpMetadata.op_name
                            op_name = next((y.decode() for b, y in layers._fields(x)
                                            if b == 2), "")
                        elif a == 35:
                            iid = x
                        elif a == 36:
                            operands += layers._ints(x)
                        elif a == 38:
                            calls += layers._ints(x)
                    instrs[iid] = (name, scope_of(op_name), operands, calls)
                    members.append(iid)
            comps[cid] = members

    def fused(cid, seen):
        votes = collections.Counter()
        for m in comps.get(cid, ()):
            _, s, _, calls = instrs[m]
            if s:
                votes[s] += 1
            for c in calls:
                if c not in seen:
                    seen.add(c)
                    votes.update(fused(c, seen))
        return votes

    memo: Dict[int, str] = {}

    def resolve(iid, depth=0):
        if iid in memo:
            return memo[iid]
        _, s, operands, calls = instrs[iid]
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


def program_scopes(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{program (module name without its fingerprint): {instruction: scope}}
    from the ``Hlo Proto`` stats of the profile's ``/host:metadata`` plane,
    keeping the scoped instructions only."""
    out: Dict[str, Dict[str, str]] = collections.defaultdict(dict)
    for f, plane in layers._fields(xspace):
        if f != 1:                                       # XSpace.planes
            continue
        fs = list(layers._fields(plane))
        if next((v for k, v in fs if k == 2), b"") != b"/host:metadata":
            continue
        stat_ids = set()
        for k, v in fs:                                  # XPlane.stat_metadata
            if k == 5:
                entry = dict(layers._fields(v))
                meta = dict(layers._fields(entry.get(2, b"")))
                if meta.get(2) == b"Hlo Proto":
                    stat_ids.add(meta.get(1, entry.get(1)))
        for k, v in fs:                                  # XPlane.event_metadata
            if k != 4:
                continue
            meta = list(layers._fields(dict(layers._fields(v)).get(2, b"")))
            name = next((x.decode() for a, x in meta if a == 2), "")
            for a, stat in meta:
                if a == 5:                               # XEventMetadata.stats
                    st = dict(layers._fields(stat))
                    if st.get(1) in stat_ids and 6 in st:
                        out[layers.module_name(name)].update(
                            (i, s) for i, s in hlo_scopes(st[6]).items() if s)
    return dict(out)


@dataclasses.dataclass
class ScopedRun(layers.Layers):
    """A traced run's ``layers.Layers`` with the model scopes of its
    programs' instructions: {program: {instruction: scope}}."""
    model_scopes: Dict[str, Dict[str, str]] = dataclasses.field(default_factory=dict)

    @staticmethod
    def of_layers(run: layers.Layers, scopes: Dict[str, Dict[str, str]]) -> "ScopedRun":
        return ScopedRun(run.ops, run.spans, run.programs, run.host, scopes)

    def scope(self, op: layers.LayerOp) -> str:
        return self.model_scopes.get(op.program, {}).get(op.name, "")

    def to_json(self) -> Dict:
        used = {(o.program, o.name) for o in self.ops}
        return {**super().to_json(),
                "model_scopes": {p: {i: s for i, s in d.items() if (p, i) in used}
                                 for p, d in self.model_scopes.items()}}

    @staticmethod
    def from_json(d: Dict) -> "ScopedRun":
        return ScopedRun.of_layers(layers.Layers.from_json(d), d["model_scopes"])


def save_json_gz(run: ScopedRun, path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(run.to_json(), f)


def load_json_gz(path: Path) -> ScopedRun:
    with gzip.open(path, "rt") as f:
        return ScopedRun.from_json(json.load(f))


@functools.lru_cache(maxsize=4)
def _load_cached(path: str, mtime_ns: int, size: int) -> ScopedRun:
    return ScopedRun.of_layers(layers._load_cached(path, mtime_ns, size),
                               program_scopes(Path(path).read_bytes()))


def of(trace: traces.Trace, trace_dir: Optional[Path] = None) -> Optional[ScopedRun]:
    """The ``ScopedRun`` of a traced run: ``trace`` itself where it is one;
    else the profile under ``trace_dir`` (``layers.TRACE_DIR``), newest
    first, whose ``bench.*`` spans are ``trace``'s."""
    if isinstance(trace, ScopedRun):
        return trace
    found = sorted(Path(trace_dir or layers.TRACE_DIR).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns, reverse=True)
    for p in found:
        st = p.stat()
        if layers._load_cached(str(p), st.st_mtime_ns, st.st_size).spans == trace.spans:
            return _load_cached(str(p), st.st_mtime_ns, st.st_size)
    return None


def scope_seconds(run: ScopedRun) -> Dict[str, float]:
    """{scope: device seconds of the local step's ops it claims in the
    window}, summed over their calls and averaged over the devices."""
    out: Dict[str, float] = collections.defaultdict(float)
    for o in layers._in_window(run):
        s = run.scope(o) if o.scope == STEP else ""
        if s:
            out[s] += o.dur / 1e9
    n = max(len(run.devices()), 1)
    return {s: v / n for s, v in out.items()}


def scope_ms_per_round(trace, ctx, scope: str) -> Optional[float]:
    """Device ms a round of the ops ``scope`` claims; None where none do."""
    run = of(trace)
    if run is None or not ctx["rounds"]:
        return None
    s = scope_seconds(run).get(scope)
    return s * 1e3 / ctx["rounds"] if s else None


def breakdown(run: ScopedRun, rounds: int) -> List[List]:
    """[[round-layer scope, model scope, ms a round]] of the window, largest
    first: where the local step's time goes."""
    out: Dict = collections.defaultdict(float)
    for o in layers._in_window(run):
        out[(o.scope, run.scope(o))] += o.dur / 1e6
    n = max(len(run.devices()), 1) * max(rounds, 1)
    return sorted(([a, b, v / n] for (a, b), v in out.items()), key=lambda r: -r[2])
