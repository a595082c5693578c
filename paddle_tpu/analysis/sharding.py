"""Sharding lint — prove the SPMD communication plan statically, from the
post-partitioning HLO, before the job ever runs.

The runtime side of this story is `profiler.trace_analysis
.collective_rows()`: a per-collective ledger parsed from a captured
device trace — visible only AFTER chips burned a step. This module is
its static twin: lower + compile a jitted executable under a mesh (CPU
host-platform meshes work — `--xla_force_host_platform_device_count=8`),
parse the optimized HLO text, and produce

  collective inventory   one row per collective instruction, SAME row
                         schema as collective_rows() (timing columns
                         None — statics have no clock), with shapes,
                         dtypes, replica groups and statically computed
                         bytes (operand + output buffer bytes per
                         device per execution — the static twin of the
                         trace's `bytes_accessed` stat)
  resharding findings    an all-gather that undoes a parameter's
                         declared sharding (the partitioner quietly
                         gathering a sharded weight to replicated —
                         either a wrong pspec or a layout conflict); the
                         finding names the parameter and the source site
  replication findings   large replicated parameters in an
                         otherwise-tensor-sharded executable, with the
                         pspec that would shard them
  CommPlan check         the inventory diffed against a declared plan
                         (analysis.commplan) — extra/missing collectives
                         are structured errors

`diff_ledgers` closes the loop: the static inventory and the runtime
trace ledger aggregate by collective kind and must agree on bytes —
the static-vs-runtime cross-check tools/graph_lint.py `comm-xcheck`
runs against the checked-in fixture.

Known limits (documented, not silent): instructions inside `while`
bodies are counted once per textual occurrence, not per trip (a scan
over microbatches under-counts); bytes are per-device buffer traffic,
not link-level ring traffic (2(n-1)/n factors are an algorithm choice
the compiler owns).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .commplan import (COLLECTIVE_KINDS, CommPlan, CommPlanError,
                       collective_kind, rows_by_kind)
from .findings import Finding, Findings

#: opcodes the inventory collects ("-start" async halves count; "-done"
#: halves are skipped — same transfer, second mention). ONE list, shared
#: with the plan checker: the inventory and CommPlan must never disagree
#: about what counts as a collective.
_COLLECTIVE_OPS = COLLECTIVE_KINDS

#: ops a value flows through unchanged (modulo layout/dtype) — the walk
#: from an all-gather back to the parameter it gathers
_PASSTHROUGH_OPS = ("copy", "bitcast", "convert", "reshape", "transpose",
                    "get-tuple-element", "optimization-barrier")
#: the subset that appears as words in XLA's generated fusion names
#: ("convert_copy_fusion.2") — a unary fusion named purely from these is
#: itself pass-through (the multi-word ops above never name fusions)
_PASSTHROUGH_FUSION_WORDS = ("convert", "copy", "bitcast", "reshape",
                             "transpose")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
}

# one typed value in an instruction line: dtype[dims]{optional layout}
_TYPED_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{[^{}]*\})?")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\(?.*?\)?)\s*"
    r"([\w\-]+)\((.*)$")
_METADATA_RE = re.compile(
    r'metadata=\{[^}]*?op_name="([^"]*)"'
    r'(?:[^}]*?source_file="([^"]*)")?'
    r'(?:[^}]*?source_line=(\d+))?'
    r'(?:[^}]*?stack_frame_id=(\d+))?')
# the module header's source tables (jax 0.9 / XLA prints an op's site as
# metadata stack_frame_id=N into these, no longer as source_file/line)
_TABLE_ROW_RE = re.compile(r'^(\d+)\s+(?:"([^"]*)"|\{([^}]*)\})\s*$')
_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[^}]*(?:\},\{[^}]*)*\}\}|\[[0-9,]+\]<=\[[^\]]*\]"
    r"(?:T\([0-9,]+\))?)")
_PARAM_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\S+)\s*parameter\((\d+)\)"
    r"(?:,\s*sharding=(\{.*?\})(?=,|\s*$))?")


def _shape_dtype(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """All (dtype, shape) values in a type string — one entry for a plain
    type, several for a tuple type."""
    out = []
    for m in _TYPED_RE.finditer(text):
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        out.append((m.group(1), dims))
    return out


def _nbytes(dtype: str, shape: Tuple[int, ...]) -> int:
    return int(np.prod(shape)) * _DTYPE_BYTES.get(dtype, 4) if shape \
        else _DTYPE_BYTES.get(dtype, 4)


def _where_of(meta: Optional[dict]) -> str:
    """The caller-chain `where` convention over HLO metadata: the op's
    source site plus the trailing op_name component ("mpu.py:131
    (dot_general)"). HLO keeps one frame, so the chain is one link."""
    if not meta:
        return ""
    parts = []
    if meta.get("source_file"):
        base = meta["source_file"].rsplit("/", 1)[-1]
        line = meta.get("source_line")
        parts.append(f"{base}:{line}" if line else base)
    op = (meta.get("op_name") or "").rsplit("/", 1)[-1]
    if op:
        parts.append(f"({op})")
    return " ".join(parts)


def _frame_site(tables, frame_id: int):
    """(file, line) of a stack frame in the module header's tables."""
    frame = tables.get("StackFrames", {}).get(frame_id) or {}
    loc = tables.get("FileLocations", {}).get(
        frame.get("file_location_id")) or {}
    file_name = tables.get("FileNames", {}).get(loc.get("file_name_id"))
    line = loc.get("line")
    return file_name, (str(line) if line is not None else None)


def _parse_groups(attrs: str) -> Tuple[str, Optional[int], Optional[int]]:
    """(raw string, num_groups, group_size) of a replica_groups attr.
    Handles both the explicit form ``{{0,1},{2,3}}`` and the iota form
    ``[4,2]<=[8]`` / ``[4,2]<=[2,4]T(1,0)``."""
    m = _REPLICA_GROUPS_RE.search(attrs)
    if not m:
        return "", None, None
    raw = m.group(1)
    if raw.startswith("{{"):
        groups = raw[1:-1].split("},{")
        sizes = [len([x for x in g.strip("{}").split(",") if x])
                 for g in groups]
        return raw, len(groups), (sizes[0] if sizes else None)
    gm = re.match(r"\[(\d+),(\d+)\]", raw)
    if gm:
        return raw, int(gm.group(1)), int(gm.group(2))
    return raw, None, None


# ------------------------------------------------------------ HLO parse

@dataclass
class HloCollective:
    """One collective instruction of the optimized module."""
    name: str
    kind: str
    out: List[Tuple[str, Tuple[int, ...]]]        # [(dtype, shape)]
    operands: List[Tuple[str, Tuple[int, ...]]]
    operand_names: List[str]
    replica_groups: str = ""
    num_groups: Optional[int] = None
    group_size: Optional[int] = None
    channel_id: Optional[int] = None
    where: str = ""

    @property
    def bytes(self) -> int:
        """Static per-device bytes per execution: operand + output buffer
        bytes — the twin of the runtime trace's `bytes_accessed` stat."""
        return (sum(_nbytes(d, s) for d, s in self.operands)
                + sum(_nbytes(d, s) for d, s in self.out))


@dataclass
class HloEntryParam:
    """One ENTRY-computation parameter with its compiled sharding."""
    index: int
    hlo_name: str
    dtype: str
    local_shape: Tuple[int, ...]
    sharding: str = ""           # raw sharding attr ("" = none recorded)
    arg_name: str = ""           # keypath from lowering metadata op_name
    global_shape: Optional[Tuple[int, ...]] = None

    @property
    def replicated(self) -> bool:
        return (not self.sharding) or "replicated" in self.sharding \
            or "maximal" in self.sharding

    @property
    def sharded(self) -> bool:
        return not self.replicated

    @property
    def local_bytes(self) -> int:
        return _nbytes(self.dtype, self.local_shape)


def _global_shape(local: Tuple[int, ...], sharding: str
                  ) -> Tuple[int, ...]:
    """Undo the tile assignment: global dim i = local dim i * tiles[i].
    `devices=[a,b,...]` may carry trailing replication tiles
    (last_tile_dim_replicate / last_tile_dims) beyond the rank — only
    the first rank entries partition data dims."""
    m = re.search(r"devices=\[([0-9,]+)\]", sharding or "")
    if not m:
        return tuple(local)
    tiles = [int(x) for x in m.group(1).split(",")]
    return tuple(d * t for d, t in zip(local, tiles[:len(local)]))


def parse_hlo(text: str) -> Tuple[List[HloCollective],
                                  Dict[int, HloEntryParam],
                                  Dict[str, Tuple[str, List[str]]]]:
    """(collectives, entry params by index, def-use map) of one optimized
    HLO module text. The def-use map is {instr_name: (opcode,
    [operand names])} over every computation — enough to walk a value
    chain; bodies/fusion computations are flat in the same namespace."""
    collectives: List[HloCollective] = []
    defs: Dict[str, Tuple[str, List[str]]] = {}
    entries: Dict[int, HloEntryParam] = {}
    types: Dict[str, str] = {}       # instr name -> its result type string
    tables: Dict[str, Dict[int, object]] = {}
    table = None
    in_entry = False
    depth_entry = 0
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            table = tables.setdefault(line, {})
            continue
        if table is not None:
            row = _TABLE_ROW_RE.match(line)
            if row:
                idx, name, fields = row.groups()
                table[int(idx)] = name if name is not None else {
                    k: int(v) for k, v in re.findall(r"(\w+)=(\d+)", fields)}
                continue
            if line.strip():
                table = None
        if line.startswith("ENTRY"):
            in_entry = True
            depth_entry = 0
            continue
        if in_entry:
            depth_entry += line.count("{") - line.count("}")
            if line.strip() == "}" and depth_entry < 0:
                in_entry = False
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, type_str, opcode, rest = m.groups()
        operand_str = rest.split(")")[0] if ")" in rest else rest
        operand_names = re.findall(r"%([\w.\-]+)", operand_str)
        defs[name] = (opcode, operand_names)
        types[name] = type_str
        pm = _PARAM_RE.match(line)
        if pm and in_entry:
            hlo_name, type_s, idx, shard = pm.groups()
            vals = _shape_dtype(type_s)
            dtype, shape = vals[0] if vals else ("f32", ())
            meta = _METADATA_RE.search(line)
            ep = HloEntryParam(
                index=int(idx), hlo_name=hlo_name, dtype=dtype,
                local_shape=shape, sharding=shard or "",
                arg_name=(meta.group(1) if meta else "") or "")
            ep.global_shape = _global_shape(ep.local_shape, ep.sharding)
            entries[ep.index] = ep
            continue
        base = opcode[:-len("-start")] if opcode.endswith("-start") \
            else opcode
        if base.endswith("-done"):
            continue
        if base not in _COLLECTIVE_OPS:
            continue
        meta_m = _METADATA_RE.search(line)
        meta = None
        if meta_m:
            meta = {"op_name": meta_m.group(1),
                    "source_file": meta_m.group(2),
                    "source_line": meta_m.group(3)}
            if meta_m.group(4) and not meta["source_file"]:
                meta["source_file"], meta["source_line"] = _frame_site(
                    tables, int(meta_m.group(4)))
        raw, ng, gs = _parse_groups(rest)
        ch = re.search(r"channel_id=(\d+)", rest)
        # operands print with their types inline, or (jax 0.9) as bare
        # names whose types are on the lines that define them
        operands = _shape_dtype(operand_str) or [
            v for n in operand_names for v in _shape_dtype(types.get(n, ""))]
        collectives.append(HloCollective(
            name=name, kind=base,
            out=_shape_dtype(type_str),
            operands=operands,
            operand_names=operand_names,
            replica_groups=raw, num_groups=ng, group_size=gs,
            channel_id=int(ch.group(1)) if ch else None,
            where=_where_of(meta)))
    return collectives, entries, defs


# ----------------------------------------------------------- inventory

def collective_inventory(text_or_parsed, executable: str = ""
                         ) -> List[dict]:
    """The static collective ledger: one row per collective instruction,
    in the EXACT row schema of trace_analysis.collective_rows() so the
    static and runtime tables diff cell for cell — timing columns are
    None (statics have no clock), `bytes` is computed from shapes.
    Extra keys (kind/dtype/shapes/replica_groups/where/group_size) ride
    along for the sharding passes and the CLI table."""
    colls = text_or_parsed[0] if isinstance(text_or_parsed, tuple) \
        else parse_hlo(text_or_parsed)[0]
    rows = []
    for c in colls:
        rows.append({
            "name": c.name, "calls": 1,
            "dur_us": None, "busy_us": None, "overlapped_us": None,
            "exposed_us": None, "exposed_frac": None,
            "bytes": c.bytes, "bus_gbps": None,
            # static-only columns
            "kind": c.kind,
            "dtype": ",".join(sorted({d for d, _ in c.out})),
            "shapes": [list(s) for _, s in c.out],
            "replica_groups": c.replica_groups,
            "group_size": c.group_size,
            "where": c.where,
        })
    rows.sort(key=lambda r: (-r["bytes"], r["name"]))
    return rows


# -------------------------------------------------------------- passes

def _walk_to_param(start_names: Sequence[str], defs, entries_by_name):
    """Follow pass-through ops from an instruction's operands back to an
    ENTRY parameter; returns the HloEntryParam or None. Unary fusions
    whose generated name is composed purely of pass-through op kinds
    ("convert_copy_fusion") count as pass-through — that is how a bf16
    parameter's f32 convert appears after fusion."""
    seen = set()
    stack = list(start_names)
    while stack:
        nm = stack.pop()
        if nm in seen:
            continue
        seen.add(nm)
        if nm in entries_by_name:
            return entries_by_name[nm]
        op, operands = defs.get(nm, (None, []))
        if op is None:
            continue
        passthrough = op in _PASSTHROUGH_OPS
        if not passthrough and op in ("fusion", "call") \
                and len(operands) == 1:
            head = nm.split(".")[0]
            words = [w for w in head.split("_")
                     if w not in ("fusion", "call")]
            passthrough = bool(words) and all(
                w in _PASSTHROUGH_FUSION_WORDS for w in words)
        if passthrough:
            stack.extend(operands)
    return None


def resharding_pass(parsed, executable: str = "",
                    param_names: Optional[Dict[str, str]] = None
                    ) -> List[Finding]:
    """Detect partitioner-inserted resharding of PARAMETERS: an
    all-gather whose input chain reaches a sharded entry parameter
    (certain), or whose operand/output shapes are exactly a sharded
    parameter's local/global shapes (strong shape evidence — the gather
    happens behind a multi-operand fusion). Either way the declared
    sharding is being undone every step: a wrong pspec on that layer, or
    an annotation the consuming op cannot honor.

    `param_names` maps lowering arg keypaths ("param_arrays[3]") to
    model-level names ("gpt.h.0.attn.qkv.weight") so the finding names
    the offending LAYER, not a flat index."""
    colls, entries, defs = parsed
    entries_by_name = {e.hlo_name: e for e in entries.values()}
    names = param_names or {}

    def disp(ep: HloEntryParam) -> str:
        return names.get(ep.arg_name) or ep.arg_name \
            or f"arg[{ep.index}]"

    out: List[Finding] = []
    for c in colls:
        if c.kind != "all-gather":
            continue
        hit = _walk_to_param(c.operand_names, defs, entries_by_name)
        certain = hit is not None and hit.sharded
        cands: List[HloEntryParam] = []
        if certain:
            cands = [hit]
        else:
            for ep in entries.values():
                if not ep.sharded or ep.global_shape is None:
                    continue
                if len(ep.local_shape) < 2:
                    continue
                if any(s == ep.global_shape for _, s in c.out) and any(
                        s == ep.local_shape for _, s in c.operands):
                    cands.append(ep)
        if not cands:
            continue
        who = " | ".join(disp(e) for e in cands[:3])
        loc = f" @ {c.where}" if c.where else ""
        out.append(Finding(
            "sharding", "param_gather", "warn",
            f"{c.name} gathers sharded parameter {who} back to "
            f"replicated ({cands[0].dtype}"
            f"{list(cands[0].global_shape or ())}, "
            f"{c.bytes / 1e6:.2f} MB/step) — the declared sharding is "
            f"undone every step"
            + ("" if certain else " (shape-matched through a fusion)"),
            where=f"{who}{loc}", executable=executable,
            data={"op": c.name, "params": [disp(e) for e in cands],
                  "bytes": c.bytes, "certain": certain,
                  "replica_groups": c.replica_groups}))
    return out


def replicated_pass(parsed, executable: str = "",
                    min_bytes: int = 1 << 20,
                    param_names: Optional[Dict[str, str]] = None,
                    mesh_axes: Optional[Dict[str, int]] = None
                    ) -> List[Finding]:
    """Flag large REPLICATED parameters in an otherwise-tensor-sharded
    executable — every device holds the full copy while its neighbors'
    parameters are sharded (the forgotten-pspec case: one 6.7B embedding
    left replicated silently costs a full HBM copy per chip). Quiet on
    purely data-parallel executables (replicated params are the design
    there): fires only when at least one floating ndim>=2 parameter IS
    sharded. With `param_names` (the TrainStep path) only mapped args
    count as parameters on BOTH sides — a dp-sharded float batch is not
    sharding evidence and a replicated batch is not a finding; without
    the mapping every floating ndim>=2 arg is treated as a parameter
    (the generic-callable approximation). The suggested pspec shards the
    largest divisible dim over the largest fitting mesh axis."""
    _, entries, _ = parsed
    names = param_names or {}
    floatish = {"f32", "f64", "f16", "bf16"}
    considered = [e for e in entries.values()
                  if not names or e.arg_name in names]
    sharded_weights = [e for e in considered
                       if e.sharded and e.dtype in floatish
                       and len(e.local_shape) >= 2]
    if not sharded_weights:
        return []
    out: List[Finding] = []
    for ep in considered:
        if ep.sharded or ep.dtype not in floatish \
                or len(ep.local_shape) < 1:
            continue
        nb = ep.local_bytes
        if nb < min_bytes:
            continue
        who = names.get(ep.arg_name) or ep.arg_name or f"arg[{ep.index}]"
        spec = None
        if mesh_axes:
            for dim in sorted(range(len(ep.local_shape)),
                              key=lambda i: -ep.local_shape[i]):
                fits = [a for a, s in mesh_axes.items()
                        if s > 1 and ep.local_shape[dim] % s == 0]
                if fits:
                    ax = max(fits, key=lambda a: mesh_axes[a])
                    spec = ["None"] * len(ep.local_shape)
                    spec[dim] = repr(ax)
                    spec = f"P({', '.join(spec)})"
                    break
        out.append(Finding(
            "sharding", "replicated_param", "warn",
            f"parameter {who} ({ep.dtype}{list(ep.local_shape)}, "
            f"{nb / 1e6:.2f} MB) is replicated on every device while "
            f"other parameters are sharded"
            + (f" — pspec {spec} would shard it" if spec else ""),
            where=who, executable=executable,
            data={"param": who, "bytes": nb,
                  "shape": list(ep.local_shape), "dtype": ep.dtype,
                  **({"suggested_pspec": spec} if spec else {})}))
    return out


# ---------------------------------------------------------------- audit

@dataclass
class ShardingAudit:
    """Everything the sharded passes proved about one compiled
    executable: the static collective ledger (`rows`), the structured
    `findings` (sharding + comm_plan passes, allowlist applied by the
    GraphLint caller), and the entry-parameter sharding table."""
    executable: str
    rows: List[dict]
    findings: Findings
    params: List[dict] = field(default_factory=list)
    plan: Optional[CommPlan] = None

    def by_kind(self) -> Dict[str, dict]:
        return rows_by_kind(self.rows)

    def table(self, top: int = 20) -> str:
        """The static ledger in the ONE collective-row format (shared
        with the runtime DistributedView/CollectiveLedger renderers)."""
        from ..profiler.trace_analysis import format_collective_rows
        lines = [f"---- Static collective inventory ({self.executable}) "
                 f"----"]
        if not self.rows:
            lines.append("no collectives in the lowered module "
                         "(single-shard program)")
            return "\n".join(lines)
        lines += format_collective_rows(self.rows, top=top)
        agg = self.by_kind()
        lines.append("per kind: " + ", ".join(
            f"{k} x{v['calls']} ({(v['bytes'] or 0) / 1e6:.2f} MB)"
            for k, v in sorted(agg.items())))
        return "\n".join(lines)

    def to_dict(self) -> dict:
        return {"executable": self.executable,
                "rows": [dict(r) for r in self.rows],
                "by_kind": {k: {kk: vv for kk, vv in v.items()
                                if kk != "names"}
                            for k, v in self.by_kind().items()},
                "findings": self.findings.to_dicts(),
                "params": list(self.params),
                "plan": repr(self.plan) if self.plan else None}


def audit_hlo(text: str, executable: str = "",
              param_names: Optional[Dict[str, str]] = None,
              plan: Optional[CommPlan] = None,
              replicated_bytes: int = 1 << 20,
              mesh_axes: Optional[Dict[str, int]] = None
              ) -> ShardingAudit:
    """Run every sharding pass over one optimized-HLO module text."""
    parsed = parse_hlo(text)
    rows = collective_inventory(parsed, executable)
    findings = Findings()
    findings.extend(resharding_pass(parsed, executable,
                                    param_names=param_names))
    findings.extend(replicated_pass(parsed, executable,
                                    min_bytes=replicated_bytes,
                                    param_names=param_names,
                                    mesh_axes=mesh_axes))
    if plan is not None:
        findings.extend(plan.check(rows, executable=executable))
    names = param_names or {}
    params = [{"index": e.index,
               "name": names.get(e.arg_name) or e.arg_name,
               "dtype": e.dtype, "local_shape": list(e.local_shape),
               "global_shape": list(e.global_shape or ()),
               "sharded": e.sharded, "sharding": e.sharding}
              for _, e in sorted(parsed[1].items())]
    return ShardingAudit(executable=executable, rows=rows,
                         findings=findings, params=params, plan=plan)


def compiled_hlo_text(fn, *args, **kwargs) -> str:
    """Optimized (post-SPMD-partitioning) HLO of a jitted callable for
    abstract args — lower + compile, nothing executes. The collectives
    only exist AFTER partitioning, so `lowered.as_text()` (StableHLO,
    annotations only) is not enough."""
    lowered = fn.lower(*args, **kwargs)
    return lowered.compile().as_text()


# ------------------------------------------------- static-vs-runtime diff

def diff_ledgers(static_rows: Sequence[dict], runtime_rows: Sequence[dict],
                 steps: Optional[int] = None, rtol: float = 0.01
                 ) -> List[dict]:
    """Diff the static inventory against a runtime trace ledger, by
    collective kind (instruction names differ between an HLO text and a
    trace capture; the kind aggregation is the stable join key). Runtime
    bytes/calls are divided by `steps` to get per-step figures; static
    rows are already per-step. Returns one dict per kind:
    {kind, static_bytes, runtime_bytes, static_calls, runtime_calls,
    rel_err, ok} — rel_err is None (and ok False) when one side is
    missing or carries no bytes."""
    div = max(steps or 1, 1)
    s = rows_by_kind(static_rows)
    r = rows_by_kind(runtime_rows)
    out = []
    for kind in sorted(set(s) | set(r)):
        sb = s.get(kind, {}).get("bytes")
        rb = r.get(kind, {}).get("bytes")
        rb_step = rb / div if rb is not None else None
        rel = None
        if sb is not None and rb_step:
            rel = abs(sb - rb_step) / rb_step
        ok = rel is not None and rel <= rtol
        out.append({"kind": kind,
                    "static_bytes": sb,
                    "runtime_bytes": rb_step,
                    "static_calls": s.get(kind, {}).get("calls", 0),
                    "runtime_calls": (r.get(kind, {}).get("calls", 0)
                                      / div),
                    "rel_err": rel, "ok": ok})
    return out
