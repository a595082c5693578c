"""GraphLint — the facade that runs every static pass over an executable
and turns findings into a report (or, in guard mode, an error) BEFORE the
job runs.

    lint = GraphLint()                        # report mode
    findings = lint.check(fn, *args, donate_argnums=(0,))
    print(findings.table("my_step"))

    GraphLint(mode="error").check(...)        # raise on any active finding

`check` accepts a plain traceable callable (args may be arrays, numpy
arrays, or jax.ShapeDtypeStructs — nothing executes, tracing is abstract)
or an already-jitted function (its own donate_argnums apply). Tracing
runs under the transfer guard, so an implicit `.item()`/`float()` inside
a Layer forward becomes a host_transfer finding naming the layer path
instead of an anonymous tracer error.

`lint_capture()` records the jitted serving executables the framework
builds while the context is active (models' `_gen_cache_get` feeds it):

    with lint_capture() as calls:
        model.prefill_paged(...); model.decode_paged(...)     # warmup
    findings = lint.check_calls(calls)

which is how the serving engine and the graph_lint CLI audit the real
prefill/decode executables without reconstructing their closures.
"""
from __future__ import annotations

import contextlib
from typing import List, Optional, Sequence, Tuple

import jax

from .findings import (Allowlist, DEFAULT_ALLOWLIST, Finding, Findings,
                       GraphLintError)
from .passes import (baked_const_pass, donation_pass, dtype_promotion_pass,
                     host_transfer_pass)
from .transfer import HostTransferError, transfer_guard

ALL_PASSES = ("host_transfer", "dtype_promotion", "baked_const", "donation")


class GraphLint:
    """Configuration + driver for the static-analysis suite.

    passes: subset of ALL_PASSES to run.
    allowlist: an Allowlist (defaults to the framework's documented
        exceptions); extra entries via `allow` (list of entry dicts).
    mode: "report" returns findings; "error" raises GraphLintError when
        any non-allowlisted finding at/above `fail_on` severity survives.
    upcast_bytes / const_bytes / donate_bytes: size thresholds for the
        dtype-promotion, baked-const and donation-candidate passes.
    """

    def __init__(self, passes: Sequence[str] = ALL_PASSES,
                 allowlist: Optional[Allowlist] = None,
                 allow: Optional[Sequence[dict]] = None,
                 mode: str = "report", fail_on: str = "warn",
                 upcast_bytes: int = 1 << 16,
                 const_bytes: int = 1 << 20,
                 donate_bytes: int = 1 << 20,
                 replicated_bytes: int = 1 << 20,
                 comm_plan=None):
        unknown = set(passes) - set(ALL_PASSES)
        if unknown:
            raise ValueError(f"unknown lint passes: {sorted(unknown)} "
                             f"(available: {ALL_PASSES})")
        if mode not in ("report", "error"):
            raise ValueError(f"mode must be 'report' or 'error', "
                             f"got {mode!r}")
        self.passes = tuple(passes)
        # `is not None`, not truthiness: an EMPTY Allowlist([]) is a
        # legitimate "no exceptions" configuration
        self.allowlist = Allowlist(
            (DEFAULT_ALLOWLIST if allowlist is None else allowlist)
            .entries)
        if allow:
            self.allowlist.entries.extend(dict(e) for e in allow)
        self.mode = mode
        self.fail_on = fail_on
        self.upcast_bytes = upcast_bytes
        self.const_bytes = const_bytes
        self.donate_bytes = donate_bytes
        # sharding lint (ISSUE 15): threshold for the large-replicated-
        # parameter pass, and an optional declared CommPlan every
        # check_sharded call verifies the inventory against
        self.replicated_bytes = replicated_bytes
        self.comm_plan = comm_plan

    @classmethod
    def coerce(cls, value) -> Optional["GraphLint"]:
        """None/False -> None; True -> report-mode lint; "error" ->
        guard-mode lint; a GraphLint passes through. (The TrainStep /
        ServingConfig `lint=` option.)"""
        if value is None or value is False:
            return None
        if value is True:
            return cls()
        if value == "error":
            return cls(mode="error")
        if isinstance(value, cls):
            return value
        raise ValueError(f"lint= expects True/'error'/GraphLint, "
                         f"got {value!r}")

    # ------------------------------------------------------------ check
    def check(self, fn, *args, donate_argnums: Sequence[int] = (),
              name: str = "", guard: bool = True, **kwargs) -> Findings:
        """Run the configured passes over one executable. Abstract: the
        function is traced (and, for the donation pass, lowered), never
        compiled or executed. guard=False skips the error-mode raise —
        for callers that store the findings first and guard themselves."""
        name = name or getattr(fn, "__name__", "fn") or "fn"
        findings = Findings()
        closed = None
        with transfer_guard() as g:
            try:
                closed = jax.make_jaxpr(fn)(*args, **kwargs)
            except HostTransferError:
                findings.extend(g.findings)
            except (jax.errors.TracerArrayConversionError,
                    jax.errors.ConcretizationTypeError,
                    jax.errors.TracerBoolConversionError) as e:
                findings.add(Finding(
                    "host_transfer", "concretization", "error",
                    f"tracing aborted on a concretization the guard "
                    f"could not attribute: {str(e).splitlines()[0]}",
                    executable=name))
        if closed is not None:
            if "host_transfer" in self.passes:
                findings.extend(host_transfer_pass(closed, name))
            if "dtype_promotion" in self.passes:
                findings.extend(dtype_promotion_pass(
                    closed, name, min_bytes=self.upcast_bytes))
            if "baked_const" in self.passes:
                findings.extend(baked_const_pass(
                    closed, name, min_bytes=self.const_bytes))
            # runs even with nothing donated: that is exactly when the
            # "donatable" advisory (large input with a same-shape output)
            # has something to say
            if "donation" in self.passes:
                findings.extend(donation_pass(
                    fn, args, donate_argnums, name,
                    min_bytes=self.donate_bytes, closed_jaxpr=closed,
                    kwargs=kwargs))
        for f in findings:
            if not f.executable:
                f.executable = name
        self.allowlist.apply(findings)
        if guard:
            self._guard(findings, name)
        return findings

    def check_calls(self, calls, dedupe: bool = True,
                    guard: bool = True) -> Findings:
        """Lint executables recorded by `lint_capture` — entries are
        (kind, jitted_fn, (args, kwargs)) with abstract (SDS) args."""
        findings = Findings()
        seen = set()
        for kind, fn, (args, kwargs) in calls:
            name = _kind_name(kind)
            key = (id(fn), name)
            if dedupe and key in seen:
                continue
            seen.add(key)
            # defer the guard until every call is checked
            findings.extend(self.check(fn, *args, name=name,
                                       guard=False, **kwargs))
        if guard:
            self._guard(findings, "captured executables")
        return findings

    # --------------------------------------------------------- sharded
    def check_sharded(self, fn, *args, name: str = "",
                      in_shardings=None, out_shardings=None,
                      donate_argnums: Sequence[int] = (),
                      param_names=None, plan=None, mesh_axes=None,
                      guard: bool = True, **kwargs):
        """Statically audit the SPMD communication plan of an executable
        lowered under a mesh (ISSUE 15): lower + compile (nothing
        executes — CPU host-platform meshes work), then run the
        sharding passes over the post-partitioning HLO — collective
        inventory, partitioner-inserted-resharding detection, the
        large-replicated-parameter pass, and the CommPlan check (`plan`
        or this linter's `comm_plan`).

        `fn` may be an already-jitted function carrying its own
        shardings (the TrainStep path) or a plain callable with
        `in_shardings`/`out_shardings` (NamedShardings — the mesh rides
        in them). Returns a ShardingAudit; its findings pass through
        the allowlist and, in guard mode, trip GraphLintError — plan
        violations raise the sharper CommPlanError."""
        from .commplan import CommPlanError
        from .sharding import audit_hlo, compiled_hlo_text
        name = name or getattr(fn, "__name__", "fn") or "fn"
        if hasattr(fn, "lower") and hasattr(fn, "__wrapped__"):
            jfn = fn
        else:
            jit_kwargs = {}
            if in_shardings is not None:
                jit_kwargs["in_shardings"] = in_shardings
            if out_shardings is not None:
                jit_kwargs["out_shardings"] = out_shardings
            jfn = jax.jit(fn, donate_argnums=tuple(donate_argnums),
                          **jit_kwargs)
        text = compiled_hlo_text(jfn, *args, **kwargs)
        audit = audit_hlo(text, executable=name,
                          param_names=param_names,
                          plan=plan if plan is not None else self.comm_plan,
                          replicated_bytes=self.replicated_bytes,
                          mesh_axes=mesh_axes)
        self.allowlist.apply(audit.findings)
        if guard and self.mode == "error":
            plan_active = audit.findings.for_pass("comm_plan") \
                .active(self.fail_on)
            if plan_active:
                raise CommPlanError(plan_active, name)
        if guard:
            self._guard(audit.findings, name)
        return audit

    def _guard(self, findings: Findings, executable: str):
        if self.mode != "error":
            return
        active = findings.active(self.fail_on)
        if active:
            raise GraphLintError(active, executable)


def _kind_name(kind) -> str:
    if isinstance(kind, tuple) and kind:
        head = str(kind[0])
        rest = ",".join(str(k) for k in kind[1:5])
        return f"{head}[{rest}]" if rest else head
    return str(kind)


def _abstract_leaf(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        # preserve MESH shardings (ISSUE 16): a sharded-serving pool's
        # NamedSharding must survive abstraction or re-lowering the
        # captured executable would silently audit the single-chip
        # program. Single-device placements are dropped deliberately —
        # they carry no SPMD information and would pin the lowering to
        # one device id.
        sh = getattr(x, "sharding", None)
        if isinstance(sh, jax.sharding.NamedSharding):
            return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype,
                                        sharding=sh)
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
    return x


@contextlib.contextmanager
def lint_capture():
    """Record every serving executable the framework jits/fetches while
    active (see models' `_gen_cache_get`): yields a list of
    (kind, jitted_fn, (abstract_args, abstract_kwargs)) entries for
    `GraphLint.check_calls`. Capturing is observation only — the calls
    still execute normally (the warmup)."""
    from ..jit import api as _api
    calls: List[Tuple] = []
    prev = _api._lint_capture_sink
    _api._lint_capture_sink = calls
    try:
        yield calls
    finally:
        _api._lint_capture_sink = prev


def _capture_record(sink, kind, fn, args, kwargs):
    """Append one abstract call record (jit/api's wrapper calls this)."""
    a = jax.tree.map(_abstract_leaf, args)
    k = jax.tree.map(_abstract_leaf, kwargs)
    sink.append((kind, fn, (a, k)))
