"""The jaxpr/HLO passes — each proves (or refutes) one framework invariant
at BUILD time, before the executable ever runs.

  host_transfer_pass    r8's "zero per-step host syncs": no callback /
                        infeed-outfeed primitive anywhere in the graph
                        (each one is a device->host round trip per step).
  dtype_promotion_pass  bf16 paths stay bf16: find convert_element_type
                        eqns that widen a LARGE low-precision tensor to
                        f32/f64 (weak-type promotions and stray astypes
                        both lower to exactly this op), with an allowlist
                        for deliberate f32 accumulations.
  baked_const_pass      no per-executable HBM duplication: closure-captured
                        arrays above a threshold that became jaxpr consts
                        get re-uploaded with EVERY executable that baked
                        them (the cached dense-twin/bench hazard).
  donation_pass         r9/r10's in-place KV updates: cross-check the
                        jit-level donated_invars against the lowered
                        module's input_output_alias table (donated but
                        unaliased = a silent copy every call) and flag
                        large non-donated inputs with a same-shape output
                        that COULD be donated.

All passes walk the jaxpr recursively (scan/cond/pjit/remat bodies
included) so an invariant can't hide inside a control-flow sub-jaxpr —
the decode loop IS a lax.scan body.
"""
from __future__ import annotations

import re
import warnings
from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np
import jax

from .findings import Finding

# primitives that force a device->host (or host->device) transfer per
# execution — any of these inside a steady-state executable breaks the
# zero-sync invariant
CALLBACK_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed", "host_local_array_to_global_array",
}
# low-precision sources and wide targets for the promotion pass
_NARROW = {"bfloat16", "float16"}
_WIDE = {"float32", "float64"}


def _source_summary(eqn, max_frames: int = 4) -> str:
    """Caller chain 'file.py:123 (fn) < file.py:88 (caller) < ...' for an
    eqn, innermost first — naming the chain (not just the innermost frame)
    is what lets an allowlist entry match on the MEANINGFUL function
    (layer_norm, attention_reference, decode_paged) instead of a lambda
    or closure body three frames down."""
    from jax._src import source_info_util
    frames = []
    # jax 0.9: user_frames walks the eqn's Traceback, not its SourceInfo
    for fr in source_info_util.user_frames(eqn.source_info.traceback):
        frames.append(f"{fr.file_name.rsplit('/', 1)[-1]}:"
                      f"{fr.start_line} ({fr.function_name})")
        if len(frames) >= max_frames:
            break
    return " < ".join(frames)


def iter_eqns(jaxpr) -> Iterable:
    """Yield every eqn in a (possibly Closed) jaxpr, descending into
    sub-jaxprs carried in eqn params (scan/while/cond/pjit/remat/custom
    vjp bodies)."""
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jx.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def _sub_jaxprs(v):
    if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _sub_jaxprs(x)


def iter_consts(jaxpr) -> Iterable:
    """Yield every const array in a closed jaxpr tree (top-level consts
    plus consts of closed sub-jaxprs, e.g. a pjit body's)."""
    consts = getattr(jaxpr, "consts", None)
    if consts:
        yield from consts
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jx.eqns:
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_consts(sub)


def _nbytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


# --------------------------------------------------------------- passes

def host_transfer_pass(closed_jaxpr, executable: str = "") -> List[Finding]:
    """Flag ops that force device<->host transfers inside the graph."""
    out = []
    for eqn in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMITIVES:
            sev = "warn" if name == "debug_callback" else "error"
            out.append(Finding(
                "host_transfer", name, sev,
                f"`{name}` forces a device<->host round trip every "
                f"execution (zero-sync invariant)",
                where=_source_summary(eqn), executable=executable))
    return out


def dtype_promotion_pass(closed_jaxpr, executable: str = "",
                         min_bytes: int = 1 << 16) -> List[Finding]:
    """Flag convert_element_type eqns widening a large narrow-precision
    tensor to f32/f64 — the lowered form of BOTH stray `astype` calls and
    weak-type promotions (jnp inserts this op for every implicit widen).
    min_bytes is the WIDENED size: small scalars/rows (loss, stats,
    positions) are free; a [B,S,H] activation or [B,V] logits copy in f32
    doubles its HBM + bandwidth."""
    out = []
    for eqn in iter_eqns(closed_jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        try:
            src = eqn.invars[0].aval
            dst = eqn.outvars[0].aval
        except Exception:
            continue
        if str(src.dtype) in _NARROW and str(dst.dtype) in _WIDE:
            wide = _nbytes(dst)
            if wide >= min_bytes:
                out.append(Finding(
                    "dtype_promotion", f"{src.dtype}_to_{dst.dtype}",
                    "warn",
                    f"{src.dtype}{list(src.shape)} widened to {dst.dtype} "
                    f"({wide / 1e6:.2f} MB) — unintended f32 upcast in a "
                    f"low-precision path?",
                    where=_source_summary(eqn), executable=executable,
                    data={"shape": list(src.shape), "from": str(src.dtype),
                          "to": str(dst.dtype), "bytes": wide}))
    return out


def baked_const_pass(closed_jaxpr, executable: str = "",
                     min_bytes: int = 1 << 20) -> List[Finding]:
    """Flag large arrays baked into the jaxpr as consts. A const is
    closure-captured data: it is embedded per-executable (re-uploaded and
    held in HBM once per compiled program that captured it), invisible to
    donation, and silently stale if the Python-side array changes."""
    out = []
    for c in iter_consts(closed_jaxpr):
        shape = getattr(c, "shape", None)
        dtype = getattr(c, "dtype", None)
        if shape is None or dtype is None:
            continue
        nb = int(np.prod(shape)) * np.dtype(dtype).itemsize if shape else 0
        if nb >= min_bytes:
            out.append(Finding(
                "baked_const", "large_const", "warn",
                f"closure-captured {dtype}{list(shape)} "
                f"({nb / 1e6:.2f} MB) baked into the jaxpr as a const — "
                f"pass it as an argument (per-executable HBM duplication)",
                executable=executable,
                data={"shape": list(shape), "dtype": str(dtype),
                      "bytes": nb}))
    return out


# ------------------------------------------------------------- donation

_ALIAS_RE = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")


def parse_io_aliases(lowered_text: str) -> Tuple[int, dict]:
    """(n_args, {flat_arg_index: output_index}) from the lowered StableHLO
    module's @main signature — the compiled input_output_alias table as
    jax records it (`tf.aliasing_output` arg attributes).

    Parsing splits the signature at `%argN:` boundaries rather than
    matching the attribute dict with a brace regex: attr VALUES contain
    nested braces (`mhlo.sharding = "{replicated}"` sorts before
    tf.aliasing_output), and a `\\{[^}]*\\}` capture would truncate at
    the first inner `}` and silently drop the alias marker for every
    sharded executable."""
    m = re.search(r"func\.func\s+public\s+@main\s*\((.*?)\)\s*->",
                  lowered_text, re.S)
    if not m:
        return 0, {}
    # parts = [prefix, idx0, seg0, idx1, seg1, ...]: each seg holds that
    # argument's type + full attribute dict, up to the next %arg
    parts = re.split(r"%arg(\d+):", m.group(1))
    aliases = {}
    n = 0
    for i in range(1, len(parts) - 1, 2):
        idx = int(parts[i])
        n = max(n, idx + 1)
        al = _ALIAS_RE.search(parts[i + 1])
        if al:
            aliases[idx] = int(al.group(1))
    return n, aliases


def parse_compiled_aliases(compiled_text: str) -> dict:
    """{entry_param_index: output_tuple_index} from a compiled HloModule
    header's ``input_output_alias={ {out}: (param, {}, may-alias), ...}``
    table. Under SPMD partitioning (num_partitions > 1) jax defers
    donation aliasing to XLA: the lowered StableHLO carries NO
    tf.aliasing_output attributes and the alias table only exists after
    compile — reading the pre-compile text alone would misreport every
    sharded executable's donation as a silent copy."""
    m = re.search(r"input_output_alias=\{((?:[^{}]|\{[^{}]*\})*)\}",
                  compiled_text)
    if not m:
        return {}
    out = {}
    for om, pm in re.findall(r"\{(\d+)[^{}]*\}:\s*\((\d+)", m.group(1)):
        out[int(pm)] = int(om)
    return out


def donation_pass(fn, args, donate_argnums: Sequence[int] = (),
                  executable: str = "", min_bytes: int = 1 << 20,
                  closed_jaxpr=None, kwargs=None) -> List[Finding]:
    """Cross-check donation intent against the lowered module's alias
    table.

    `fn` may be a plain callable (donate_argnums tells the pass what the
    caller INTENDS to donate; the pass jits with keep_unused=True so flat
    argument indices map 1:1 onto the lowered signature) or an
    already-jitted function (its own donate_argnums apply).

    Findings:
      donated_unaliased (warn)  — a donated buffer XLA did not alias: the
                                  donation silently degrades to a copy
                                  every call (shape/dtype matches no
                                  output, or the output went elsewhere).
      donatable (info)          — a large non-donated input whose exact
                                  shape+dtype appears among the outputs:
                                  if the caller never reads it after the
                                  call, donating it lets XLA reuse the
                                  buffer in place (the KV-pool pattern).
    """
    kwargs = kwargs or {}
    jitted = hasattr(fn, "lower") and hasattr(fn, "__wrapped__")
    if jitted:
        jfn = fn
    else:
        jfn = jax.jit(fn, donate_argnums=tuple(donate_argnums),
                      keep_unused=True)

    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        lowered = jfn.lower(*args, **kwargs)
    text = lowered.as_text()
    n_args, aliases = parse_io_aliases(text)

    # flat leaves in call order, tagged with which top-level arg they
    # belong to and whether that arg was donated
    flat_leaves, _ = jax.tree.flatten((args, kwargs))
    donated_set = set()
    if jitted:
        # read intent from the pjit params (donated_invars is flat) —
        # reuse the caller's already-traced jaxpr when its top eqn is the
        # pjit of this function; re-trace only as a fallback
        try:
            cj = closed_jaxpr
            if cj is None or not cj.eqns \
                    or "donated_invars" not in cj.eqns[0].params:
                cj = jax.make_jaxpr(jfn)(*args, **kwargs)
            din = cj.eqns[0].params.get("donated_invars", ())
            donated_set = {i for i, d in enumerate(din) if d}
        except Exception:
            donated_set = set()
        flat_donated = [i in donated_set for i in range(len(flat_leaves))]
    else:
        flat_donated = []
        for ai, a in enumerate(args):
            leaves = jax.tree.flatten(a)[0]
            flat_donated += [ai in set(donate_argnums)] * len(leaves)
        flat_donated += [False] * (len(flat_leaves) - len(flat_donated))

    if not aliases and any(flat_donated):
        # No aliases in the StableHLO but donation was intended: under
        # SPMD partitioning the alias table is only established at
        # compile time (see parse_compiled_aliases) — compile before
        # claiming the donation degraded to a copy. Failure-path only:
        # executables whose donation lowered normally never pay this.
        try:
            aliases = parse_compiled_aliases(lowered.compile().as_text())
        except Exception:
            pass

    out: List[Finding] = []
    mapped = n_args == len(flat_leaves)
    if not mapped:
        # pruned/transformed signature: fall back to counting — every
        # donated invar should have produced one alias attr
        n_donated = sum(flat_donated)
        if n_donated and len(aliases) < n_donated:
            out.append(Finding(
                "donation", "donated_unaliased", "warn",
                f"{n_donated - len(aliases)} of {n_donated} donated "
                f"buffers have no input_output_alias in the lowered "
                f"module (silent copy per call)",
                executable=executable,
                data={"donated": n_donated, "aliased": len(aliases)}))
        return out

    out_avals = []
    if closed_jaxpr is None:
        try:
            closed_jaxpr = jax.make_jaxpr(jfn if jitted else fn)(
                *args, **kwargs)
        except Exception:
            closed_jaxpr = None
    if closed_jaxpr is not None:
        out_avals = [(tuple(v.aval.shape), str(v.aval.dtype))
                     for v in closed_jaxpr.jaxpr.outvars]

    for i, leaf in enumerate(flat_leaves):
        aval = jax.api_util.shaped_abstractify(leaf) \
            if not hasattr(leaf, "shape") else leaf
        nb = _nbytes(aval)
        key = (tuple(aval.shape), str(aval.dtype))
        if flat_donated[i]:
            if i not in aliases:
                out.append(Finding(
                    "donation", "donated_unaliased", "warn",
                    f"donated arg {i} ({aval.dtype}{list(aval.shape)}, "
                    f"{nb / 1e6:.2f} MB) has no input_output_alias — "
                    f"XLA copies it every call instead of updating in "
                    f"place",
                    where=f"arg[{i}]", executable=executable,
                    data={"arg": i, "shape": list(aval.shape),
                          "dtype": str(aval.dtype), "bytes": nb}))
        elif nb >= min_bytes and key in out_avals:
            out.append(Finding(
                "donation", "donatable", "info",
                f"arg {i} ({aval.dtype}{list(aval.shape)}, "
                f"{nb / 1e6:.2f} MB) is not donated but an output has "
                f"its exact shape+dtype — donate it if it is never read "
                f"after the call",
                where=f"arg[{i}]", executable=executable,
                data={"arg": i, "shape": list(aval.shape),
                      "dtype": str(aval.dtype), "bytes": nb}))
    # surface jax's own "donated buffers not usable" warning as data
    for w in wlog:
        if "donated" in str(w.message).lower():
            if not any(f.code == "donated_unaliased" for f in out):
                out.append(Finding(
                    "donation", "donated_unaliased", "warn",
                    str(w.message).split("\n")[0],
                    executable=executable))
    return out
