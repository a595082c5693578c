"""Structured findings — the one result schema every static pass emits.

A Finding is one provable (or strongly-indicated) fact about an executable:
a host transfer inside a traced region, a donated buffer XLA could not
alias, a bf16 tensor silently upcast to f32, a closure-captured array baked
into the jaxpr as a const, a signature delta that will force a recompile,
or an invalid serving configuration. Every producer — the jaxpr/HLO passes
(analysis.passes), the recompile differ (analysis.recompile), the transfer
guard (analysis.transfer), and config validation (inference.ServingConfig)
— speaks this schema, so one table renderer, one allowlist format and one
guard-mode error serve the whole suite.

Allowlist: some findings describe DELIBERATE behavior (f32 softmax
accumulation in a bf16 model, the sampling head's f32 logits). An
Allowlist entry is {"pass": <pass name>, "code": <finding code or "*">,
"where": <substring of the finding's location>, "reason": <why this is
fine>} — matched findings stay in the report marked allowed (with the
reason) but never trip guard mode. DEFAULT_ALLOWLIST documents the
framework's own deliberate exceptions.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

#: severity order for guard thresholds
SEVERITIES = ("info", "warn", "error")


@dataclass
class Finding:
    """One static-analysis result.

    pass_name: which pass produced it (host_transfer | donation |
        dtype_promotion | baked_const | recompile_hazard | config |
        source_lint).
    code: short machine-matchable slug within the pass (e.g.
        "donated_unaliased", "bf16_to_f32", "tracer_item").
    severity: "error" (invariant broken), "warn" (probable hazard),
        "info" (advisory, e.g. a donation candidate).
    message: one human sentence; says what AND where.
    where: the location — a source summary ("gpt.py:123 (forward)"), a
        layer path ("GPTForCausalLM/gpt/h/0/attn"), or an argument name.
    executable: name of the audited executable ("decode_paged[...]").
    data: pass-specific details (shapes, dtypes, byte counts, indices).
    allowed/allow_reason: set when an Allowlist entry matched.
    """
    pass_name: str
    code: str
    severity: str
    message: str
    where: str = ""
    executable: str = ""
    data: Dict = field(default_factory=dict)
    allowed: bool = False
    allow_reason: Optional[str] = None

    def to_dict(self) -> dict:
        d = {"pass": self.pass_name, "code": self.code,
             "severity": self.severity, "message": self.message}
        if self.where:
            d["where"] = self.where
        if self.executable:
            d["executable"] = self.executable
        if self.data:
            d["data"] = self.data
        if self.allowed:
            d["allowed"] = True
            d["allow_reason"] = self.allow_reason
        return d

    def __str__(self):
        tag = f"[{self.pass_name}:{self.code}]"
        loc = f" @ {self.where}" if self.where else ""
        ex = f" in {self.executable}" if self.executable else ""
        allow = f" (allowed: {self.allow_reason})" if self.allowed else ""
        return f"{self.severity.upper()} {tag} {self.message}{loc}{ex}{allow}"


class Allowlist:
    """Ordered allow entries; first match wins.

    Entries are dicts: {"pass": name, "code": code-or-"*",
    "where": substring-or-"", "reason": text}. `apply` marks matched
    findings allowed in place (the report keeps them — an allowlist is
    documentation, not deletion)."""

    def __init__(self, entries: Optional[Sequence[dict]] = None):
        self.entries = [dict(e) for e in (entries or [])]

    def __len__(self):
        return len(self.entries)

    def add(self, pass_name: str, code: str = "*", where: str = "",
            reason: str = ""):
        self.entries.append({"pass": pass_name, "code": code,
                             "where": where, "reason": reason})
        return self

    def extend(self, other: "Allowlist") -> "Allowlist":
        self.entries.extend(other.entries)
        return self

    def match(self, f: Finding) -> Optional[dict]:
        for e in self.entries:
            if e.get("pass") not in ("*", f.pass_name):
                continue
            if e.get("code", "*") not in ("*", f.code):
                continue
            where = e.get("where", "")
            if where and where not in (f.where or "") \
                    and where not in (f.executable or ""):
                continue
            return e
        return None

    def apply(self, findings: Sequence[Finding]) -> List[Finding]:
        for f in findings:
            e = self.match(f)
            if e is not None:
                f.allowed = True
                f.allow_reason = e.get("reason") or "allowlisted"
        return list(findings)

    @classmethod
    def from_json(cls, path: str) -> "Allowlist":
        with open(path) as fh:
            return cls(json.load(fh))


#: The framework's own documented exceptions — each entry is a deliberate
#: design decision, not an oversight. Format doubles as the user example.
DEFAULT_ALLOWLIST = Allowlist([
    # Sampling runs on f32 logits by design: argmax tie-breaking, top-p
    # cumulative sums and jax.random.categorical all assume f32 — the [B,V]
    # upcast happens once per sampled token, not per layer.
    {"pass": "dtype_promotion", "code": "*", "where": "sample_logits",
     "reason": "next-token sampling is deliberately f32 (argmax ties, "
               "top-p cumsum, categorical)"},
    {"pass": "dtype_promotion", "code": "*", "where": "prefill",
     "reason": "per-row last-real-position logits are gathered in f32 for "
               "the sampling head (one [B,V] row set per prefill)"},
    {"pass": "dtype_promotion", "code": "*", "where": "decode_",
     "reason": "the decode loop reads ONE [B,V] logits row in f32 per "
               "sampled token (sampling-head precision, not a layer "
               "upcast)"},
    {"pass": "dtype_promotion", "code": "*", "where": "generate_static",
     "reason": "the decode loop reads ONE [B,V] logits row in f32 per "
               "sampled token (sampling-head precision, not a layer "
               "upcast)"},
    {"pass": "dtype_promotion", "code": "*", "where": "optimizer.py",
     "reason": "optimizer update math runs in f32 on low-precision "
               "params (master-precision update; moments store f32 or "
               "int8 codes by config)"},
    # Softmax / layernorm / loss accumulate in f32 deliberately — the
    # classic bf16-training exceptions (see ops.attention score_dtype and
    # incubate fused_linear_cross_entropy).
    {"pass": "dtype_promotion", "code": "*", "where": "softmax",
     "reason": "softmax accumulates in f32 (numeric range)"},
    {"pass": "dtype_promotion", "code": "*", "where": "layer_norm",
     "reason": "layernorm moments accumulate in f32"},
    {"pass": "dtype_promotion", "code": "*", "where": "norm.py",
     "reason": "normalization moments accumulate in f32"},
    {"pass": "dtype_promotion", "code": "*", "where": "loss",
     "reason": "loss/CE reductions accumulate in f32"},
    {"pass": "dtype_promotion", "code": "*", "where": "cross_entropy",
     "reason": "CE softmax/logsumexp accumulates in f32"},
    {"pass": "dtype_promotion", "code": "*", "where": "attention",
     "reason": "attention probabilities/score reductions may accumulate "
               "in f32 (score_dtype policy)"},
    {"pass": "dtype_promotion", "code": "*", "where": "train_step.py",
     "reason": "grad-norm/clip/stats reductions accumulate in f32 "
               "(scalar-output reductions of grads)"},
    {"pass": "dtype_promotion", "code": "*", "where": "sentinel.py",
     "reason": "numerics sentinel rows reduce in f32 by design"},
    # Sharding lint (ISSUE 15): under tensor parallelism the partitioner
    # may gather the VOCAB-SHARDED embedding table for the row lookup
    # (and its tied-head/optimizer twins) instead of the masked-lookup+
    # psum form — bounded by vocab x hidden and acceptable at current
    # scales; a shard_map masked lookup is the fix when 50k-vocab tables
    # make this the top ledger row. Scoped to wte so a gather of any
    # OTHER layer's weight still fails lint.
    {"pass": "sharding", "code": "param_gather", "where": "wte",
     "reason": "vocab-parallel embedding lookup: XLA may gather the "
               "table (bounded by vocab x hidden); masked-lookup+psum "
               "via shard_map is the planned fix at real vocab sizes"},
])


class Findings:
    """An ordered collection of Finding with filtering + table rendering."""

    def __init__(self, items: Optional[Sequence[Finding]] = None):
        self.items: List[Finding] = list(items or [])

    def __iter__(self):
        return iter(self.items)

    def __len__(self):
        return len(self.items)

    def __bool__(self):
        return bool(self.items)

    def __getitem__(self, i):
        return self.items[i]

    def add(self, *findings: Finding) -> "Findings":
        self.items.extend(findings)
        return self

    def extend(self, other) -> "Findings":
        self.items.extend(list(other))
        return self

    def for_pass(self, pass_name: str) -> "Findings":
        return Findings([f for f in self.items if f.pass_name == pass_name])

    def active(self, min_severity: str = "warn") -> "Findings":
        """Non-allowlisted findings at/above the severity threshold — the
        set guard mode trips on."""
        lvl = SEVERITIES.index(min_severity)
        return Findings([f for f in self.items if not f.allowed
                         and SEVERITIES.index(f.severity) >= lvl])

    def to_dicts(self) -> List[dict]:
        return [f.to_dict() for f in self.items]

    def grouped(self) -> "Findings":
        """Collapse repeats of one site: findings sharing (pass, code,
        where, executable, allowed) merge into one carrying
        data["count"] — 24 layer_norm rows read as one line, not 24."""
        order, by_key = [], {}
        for f in self.items:
            key = (f.pass_name, f.code, f.where, f.executable, f.allowed)
            g = by_key.get(key)
            if g is None:
                g = Finding(f.pass_name, f.code, f.severity, f.message,
                            where=f.where, executable=f.executable,
                            data=dict(f.data), allowed=f.allowed,
                            allow_reason=f.allow_reason)
                g.data["count"] = 0
                by_key[key] = g
                order.append(g)
            g.data["count"] += 1
        for g in order:
            if g.data["count"] > 1:
                g.message = f"[x{g.data['count']}] {g.message}"
        return Findings(order)

    def table(self, title: Optional[str] = None) -> str:
        """Fixed-width findings table (the CLI output)."""
        lines = []
        if title:
            lines.append(title)
        if not self.items:
            lines.append("  (clean — no findings)")
            return "\n".join(lines)
        rows = []
        for f in self.items:
            sev = f.severity.upper() + ("*" if f.allowed else "")
            rows.append((sev, f"{f.pass_name}:{f.code}",
                         f.executable or "-", f.message
                         + (f" [allowed: {f.allow_reason}]"
                            if f.allowed else "")))
        w0 = max(len(r[0]) for r in rows)
        w1 = max(len(r[1]) for r in rows)
        w2 = min(max(len(r[2]) for r in rows), 28)
        for r in rows:
            lines.append(f"  {r[0]:<{w0}}  {r[1]:<{w1}}  "
                         f"{r[2][:w2]:<{w2}}  {r[3]}")
        return "\n".join(lines)


class GraphLintError(RuntimeError):
    """Guard mode tripped: the executable violates a linted invariant."""

    def __init__(self, findings: Findings, executable: str = ""):
        self.findings = findings
        self.executable = executable
        head = (f"graph lint failed for {executable}: "
                if executable else "graph lint failed: ")
        msg = head + f"{len(findings)} finding(s)\n" + \
            "\n".join(f"  {f}" for f in findings)
        super().__init__(msg)


class ConfigValidationError(ValueError):
    """A configuration the engine cannot serve — carries the same Finding
    schema as the graph passes so tools print WHY, not just that it failed
    (ValueError subclass: existing `except ValueError` callers keep
    working)."""

    def __init__(self, finding: Finding):
        self.finding = finding
        super().__init__(str(finding))
