"""Static invariant audit (ISSUE 7): analyzers, fixtures, runtime guards.

The contract under test:

* each analyzer produces **exactly one** structured finding on its
  known-bad fixture — a missing lowering cell, an overflowing accumulator,
  a hidden host sync, an under-keyed jit cache — and none on a corrected
  twin;
* the self-audit is clean: ``python -m repro.audit`` exits 0 on this repo
  under **all six analyzers** and in every ``REPRO_KERNELS`` mode (the
  acceptance gate CI enforces with the ``AUDIT.json`` artifact);
* the kernel verifier (kernelspec) and shard-partition verifier
  (sharddisjoint) each flag their sabotage fixture with exactly one
  finding: widened halo, overlapping grid writes, in-kernel output
  multiply, double-owned payload word, world-scaled Σq² overflow;
* stale ``waive(...)`` / ``invariant(...)`` declarations surface as
  warnings (exit stays 0), and ``--only`` restricts the analyzer set;
* ``oplib.register_op`` rejects malformed OpSpecs at registration time
  with an error naming the offending (stage, scheme-family) cell, without
  mutating the registries;
* the streaming capacity guard: appends past the audited int32
  ``TemporalSummary`` bound raise :class:`SummaryCapacityError` *before*
  mutating the stream, and the runtime formula agrees with the audit's.
"""
from dataclasses import replace

import numpy as np
import pytest

from repro import audit
from repro.audit import (intwidth, jitkeys, kernelspec, registry, runner,
                         sharddisjoint, tracesafety)
from repro.audit.findings import SCHEMA_VERSION, AuditReport, Finding
from repro.comm.hom_collectives import PSUM_CONTAINER_MAX, worst_case_psum
from repro.core import oplib
from repro.core.oplib import OpSpec
from repro.core.stages import Scheme, Stage
from repro.kernels import ops as kops
from repro.kernels.specs import KERNEL_SPECS, HaloRead, TileSpec
from repro.shard import exec as shard_exec
from repro.shard.placement import BlockPlacement
from repro.stream.temporal import (SummaryCapacityError, TemporalField,
                                   summary_capacity)

INT32_MAX = 2**31 - 1


def _field_spec(name, *, feasible, lower, closure="default"):
    if closure == "default":
        closure = lambda s, st, a: "cover"  # noqa: E731
    return OpSpec(name=name, arity="field", category="statistic",
                  feasible=feasible, closure=closure, lower=lower)


def _only_hszp_at_f(scheme):
    s = Scheme(scheme)
    return (Stage.F,) if (s.is_lorenzo and not s.is_nd) else ()


# ===========================================================================
# analyzer (1): registry completeness
# ===========================================================================

class TestRegistryAnalyzer:
    def test_missing_lowering_cell_one_finding(self):
        bad = _field_spec("badop", feasible=_only_hszp_at_f, lower={})
        fs = registry.analyze_registry({"badop": bad}, {},
                                       check_matrix=False)
        assert len(fs) == 1
        (f,) = fs
        assert f.invariant == "missing-lowering-rule"
        assert "(stage F, lorenzo)" in f.message

    def test_shadowed_any_rule_one_finding(self):
        rule = lambda ctx, axis: None  # noqa: E731
        bad = _field_spec("shadow", feasible=_only_hszp_at_f,
                          lower={(Stage.F, "lorenzo"): rule,
                                 (Stage.F, "any"): rule})
        fs = registry.analyze_registry({"shadow": bad}, {},
                                       check_matrix=False)
        assert [f.invariant for f in fs] == ["ambiguous-lowering-rule"]

    def test_missing_closure_one_finding(self):
        rule = lambda ctx, axis: None  # noqa: E731
        bad = _field_spec("noclose", feasible=_only_hszp_at_f,
                          lower={(Stage.F, "any"): rule}, closure=None)
        fs = registry.analyze_registry({"noclose": bad}, {},
                                       check_matrix=False)
        assert [f.invariant for f in fs] == ["missing-closure"]

    def test_registry_collision_detected(self):
        rule = lambda ctx, axis: None  # noqa: E731
        ok = _field_spec("dup", feasible=_only_hszp_at_f,
                         lower={(Stage.F, "any"): rule})
        tok = OpSpec(name="dup", arity="temporal", category="statistic",
                     feasible=lambda s: (Stage.Q,),
                     lower_temporal=lambda s, e: None)
        fs = registry.analyze_registry({"dup": ok}, {"dup": tok},
                                       check_matrix=False)
        assert [f.invariant for f in fs] == ["registry-collision"]

    def test_live_registries_clean(self):
        assert registry.analyze_registry() == []


# ===========================================================================
# analyzer (2): integer-width abstract interpretation
# ===========================================================================

class TestIntWidthAnalyzer:
    def test_default_envelope_clean(self):
        assert intwidth.analyze_int_width(probe_runtime=False) == []

    def test_overflowing_sumsq_one_finding_per_scheme(self):
        env = intwidth.Envelope(max_slab_steps=129)  # 129 * 4095**2 > 2^31
        fs = intwidth.analyze_int_width(env, probe_runtime=False)
        assert len(fs) == len(list(Scheme))
        assert {f.invariant for f in fs} == {"sumsq-overflow"}
        assert {f.subject for f in fs} == {"temporal.q_sumsq"}

    def test_field_sum_overflow_detected(self):
        # metadata/residual sums over a 2^21-element field at |q|<=4095
        # exceed int32 only for the blockmean schemes (Lorenzo contracts
        # its stage-(2) statistics through f32)
        env = intwidth.Envelope(max_field_elems=2**21, max_slab_steps=1)
        fs = intwidth.analyze_int_width(env, probe_runtime=False)
        assert fs, "expected blockmean accumulator overflows"
        assert {f.invariant for f in fs} == {"sum-overflow"}
        assert all("hszx" in f.message for f in fs)

    def test_safe_size_table_shape(self):
        table = intwidth.safe_size_table()
        for scheme in Scheme:
            row = table[scheme.value]
            assert row["max_safe_slab_steps"] >= 128
            assert row["summary_capacity"] == summary_capacity(4095)
            assert row["accumulators"]["temporal.q_sumsq"]["dtype"] == "int32"
        # Lorenzo residuals grow 2^nd-fold; blockmean residuals 2-fold
        assert table["hszp_nd"]["residual_abs_max"] == 8 * 4095
        assert table["hszx"]["residual_abs_max"] == 2 * 4095

    def test_runtime_guard_probe_clean(self):
        assert intwidth.analyze_int_width() == []

    def test_interval_arithmetic(self):
        iv = intwidth.Interval.sym(10)
        assert (iv * iv).hi == 100
        assert iv.square().lo == 0
        assert iv.sum_n(3).mag == 30
        assert iv.zigzag() == intwidth.Interval(0, 20)
        with pytest.raises(ValueError):
            intwidth.Interval(1, 0)


# ===========================================================================
# analyzer (3): trace-safety lint
# ===========================================================================

_HOST_SYNC_FIXTURE = '''
import jax

@jax.jit
def f(x):
    return x.item()
'''

_TRACER_BRANCH_FIXTURE = '''
import jax
import jax.numpy as jnp

@jax.jit
def f(x):
    s = jnp.sum(x)
    if s > 0:
        return s
    return -s
'''

_WAIVED_FIXTURE = '''
import jax

@jax.jit
def f(x):
    return x.item()  # audit: waive(host-sync) deliberate for this test
'''

_KERNEL_HOST_SYNC_FIXTURE = '''
import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

def _kern(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2

@functools.partial(jax.jit, static_argnames=("interpret",))
def double2d(x, *, interpret=False):
    n0, n1 = x.shape
    out = pl.pallas_call(
        _kern, out_shape=jax.ShapeDtypeStruct((n0, n1), x.dtype),
        interpret=interpret)(x)
    peak = jnp.max(out)
    if peak.item() > 0:  # host sync inside the jitted wrapper
        return out
    return -out
'''

_SHARD_BODY_HOST_SYNC_FIXTURE = '''
import jax
import jax.numpy as jnp
from repro import compat

def merge(mesh, stripes):
    def body(st):
        buf = jax.lax.psum(st, "shard")
        peak = jnp.max(buf)
        if peak.item() > 0:  # host sync inside the collective body
            return buf
        return -buf
    f = jax.jit(compat.shard_map(body, mesh=mesh,
                                 in_specs=None, out_specs=None))
    return f(stripes)
'''

_CLEAN_RULE_FIXTURE = '''
import jax.numpy as jnp

def _mean_rule(ctx, axis):
    if ctx.plan is not None and not ctx.plan.aligned:
        return None
    if ctx.scheme.is_nd:
        n = int(ctx.shape[0])
        return jnp.sum(jnp.ones(n))
    return jnp.where(jnp.asarray(0) > 0, 1.0, 0.0)
'''


class TestTraceSafetyAnalyzer:
    def test_hidden_host_sync_one_finding(self):
        fs = tracesafety.lint_source(_HOST_SYNC_FIXTURE, "fix.py")
        assert len(fs) == 1
        assert fs[0].invariant == "host-sync"
        assert fs[0].file == "fix.py" and fs[0].line is not None

    def test_tracer_branch_one_finding(self):
        fs = tracesafety.lint_source(_TRACER_BRANCH_FIXTURE, "fix.py")
        assert [f.invariant for f in fs] == ["tracer-branch"]

    def test_waiver_comment_suppresses(self):
        assert tracesafety.lint_source(_WAIVED_FIXTURE, "fix.py") == []

    def test_static_branches_not_flagged(self):
        assert tracesafety.lint_source(_CLEAN_RULE_FIXTURE, "fix.py") == []

    def test_repo_is_trace_safe(self):
        assert tracesafety.analyze_trace_safety() == []

    def test_kernel_wrapper_host_sync_caught(self):
        """A host sync hidden inside a jitted Pallas-kernel wrapper is a
        finding — the analyzer must not treat kernel wrappers specially."""
        fs = tracesafety.lint_source(_KERNEL_HOST_SYNC_FIXTURE, "kern.py")
        assert [f.invariant for f in fs] == ["host-sync"]
        assert fs[0].line is not None

    def test_kernels_package_in_audit_roots(self):
        """src/repro/kernels is part of the default trace-safety sweep, so
        regressions in the fused-kernel wrappers surface in repro.audit."""
        assert "kernels" in tracesafety._DEFAULT_ROOTS

    def test_comm_and_shard_packages_in_audit_roots(self):
        """The collective (comm) and sharded-store (shard) packages run
        shard_map-traced bodies, so they are linted by default too."""
        assert "comm" in tracesafety._DEFAULT_ROOTS
        assert "shard" in tracesafety._DEFAULT_ROOTS

    def test_shard_map_body_host_sync_caught(self):
        """A host sync inside a shard_map body (the sharded store's program
        shape) is a finding — collective bodies trace like any jitted fn."""
        fs = tracesafety.lint_source(_SHARD_BODY_HOST_SYNC_FIXTURE,
                                     "shardfix.py")
        assert [f.invariant for f in fs] == ["host-sync"]
        assert fs[0].line is not None


# ===========================================================================
# analyzer (4): jit-cache-key soundness
# ===========================================================================

_UNDERKEYED_FIXTURE = '''
import jax

class Engine:
    def __init__(self):
        self._jitted = {}

    def go(self, fields, scale):
        key = (len(fields),)
        fn = self._jitted.get(key)
        if fn is None:
            def run(*flat, _s=scale):
                return [x * _s for x in flat]
            fn = jax.jit(run)
            self._jitted[key] = fn
        return fn(*fields)
'''


class TestJitKeyAnalyzer:
    def test_underkeyed_cache_one_finding(self):
        fs = jitkeys.analyze_source(_UNDERKEYED_FIXTURE, "fix.py")
        assert len(fs) == 1
        assert fs[0].invariant == "unkeyed-closure"
        assert fs[0].subject == "scale"

    def test_keyed_twin_clean(self):
        good = _UNDERKEYED_FIXTURE.replace("key = (len(fields),)",
                                           "key = (len(fields), scale)")
        assert jitkeys.analyze_source(good, "fix.py") == []

    def test_invariant_comment_waives(self):
        waived = _UNDERKEYED_FIXTURE.replace(
            "fn = jax.jit(run)",
            "fn = jax.jit(run)  # audit: invariant(scale)")
        assert jitkeys.analyze_source(waived, "fix.py") == []

    def test_sabotaged_engine_key_detected(self):
        # dropping seed_sig from the key built at the run() call site must
        # surface `seeds` as an unkeyed traced input (the PR 3/5 bug class)
        from pathlib import Path

        import repro

        engine = (Path(repro.__file__).parent / "analytics"
                  / "engine.py").read_text()
        sabotaged = engine.replace("region, seed_sig)", "region, None)")
        assert sabotaged != engine
        fs = jitkeys.analyze_source(sabotaged, "engine.py")
        assert any(f.subject == "seeds" and f.invariant == "unkeyed-closure"
                   for f in fs)

    def test_repo_cache_keys_sound(self):
        assert jitkeys.analyze_jit_keys() == []


# ===========================================================================
# runner / CLI / self-audit
# ===========================================================================

class TestRunner:
    def test_self_audit_zero_findings(self):
        report = audit.run_audit()
        assert report.ok, "\n".join(f.render() for f in report.findings)
        assert report.safe_sizes  # table attached even when clean

    def test_cli_clean_exit_and_json(self, tmp_path, capsys):
        out = tmp_path / "AUDIT.json"
        rc = runner.main(["--json", str(out)])
        assert rc == 0
        import json

        data = json.loads(out.read_text())
        assert data["ok"] and data["n_findings"] == 0
        assert set(data["safe_sizes"]) >= {s.value for s in Scheme}

    def test_cli_nonzero_on_findings(self, capsys):
        # a 129-step envelope genuinely overflows Σq² — the CLI must fail
        rc = runner.main(["--analyzer", "intwidth",
                          "--max-slab-steps", "129"])
        assert rc == 1
        assert "sumsq-overflow" in capsys.readouterr().out

    def test_report_round_trip(self):
        f = Finding("registry", "missing-lowering-rule", "msg", subject="op")
        rep = AuditReport(findings=[f])
        d = rep.to_dict()
        assert not d["ok"] and d["findings_by_analyzer"] == {"registry": 1}
        assert f.render().startswith("[registry/missing-lowering-rule]")


# ===========================================================================
# satellite: registration-time validation
# ===========================================================================

class TestRegisterOpValidation:
    def test_rejects_missing_cell_naming_it(self):
        bad = _field_spec("badreg", feasible=_only_hszp_at_f, lower={})
        with pytest.raises(ValueError, match=r"\(stage F, lorenzo\)"):
            oplib.register_op(bad)
        assert "badreg" not in oplib.OPS
        assert "badreg" not in oplib._ALL_OPS

    def test_rejects_missing_closure(self):
        rule = lambda ctx, axis: None  # noqa: E731
        bad = _field_spec("badreg2", feasible=_only_hszp_at_f,
                          lower={(Stage.F, "any"): rule}, closure=None)
        with pytest.raises(ValueError, match="closure"):
            oplib.register_op(bad)
        assert "badreg2" not in oplib.OPS

    def test_rejects_temporal_without_rule(self):
        bad = OpSpec(name="badtemp", arity="temporal", category="statistic",
                     feasible=lambda s: (Stage.Q,))
        with pytest.raises(ValueError, match="lower_temporal"):
            oplib.register_op(bad)
        assert "badtemp" not in oplib.TEMPORAL_OPS

    def test_accepts_wellformed_spec(self):
        rule = lambda ctx, axis: None  # noqa: E731
        ok = _field_spec("okreg_audit", feasible=_only_hszp_at_f,
                         lower={(Stage.F, "any"): rule})
        try:
            oplib.register_op(ok)
            assert "okreg_audit" in oplib.OPS
            assert registry.analyze_registry() == []
        finally:
            oplib.OPS.pop("okreg_audit", None)
            oplib._ALL_OPS.pop("okreg_audit", None)
            oplib._ORDER.pop("okreg_audit", None)


# ===========================================================================
# satellite: TemporalSummary capacity guard
# ===========================================================================

class TestSummaryCapacityGuard:
    def test_formula_matches_audit(self):
        for q_abs in (0, 1, 255, 4095, 4096, 2**15, 2**20):
            assert summary_capacity(q_abs) == intwidth.summary_capacity(q_abs)
        assert summary_capacity(4095) == INT32_MAX // 4095**2 == 128
        assert summary_capacity(0) == INT32_MAX
        with pytest.raises(ValueError):
            summary_capacity(-1)

    def test_append_fails_loudly_at_boundary(self):
        # a tiny eps drives |q| to ~2^15, so capacity is O(1) timesteps:
        # the guard must reject the append that crosses it, untouched state
        data = np.linspace(0.5, 1.0, 256, dtype=np.float32).reshape(1, 256)
        tf = TemporalField("hszx", eps=2**-16)
        tf.append(data)
        q_abs = tf._q_abs_max
        cap = summary_capacity(q_abs)
        assert 1 <= cap <= 8, f"fixture drifted: capacity {cap}"
        while tf.n_steps < cap:
            tf.append(data)
        steps_before = tf.n_steps
        n_slabs = tf.n_slabs
        with pytest.raises(SummaryCapacityError, match="capacity"):
            tf.append(data)
        assert tf.n_steps == steps_before  # stream not mutated
        assert tf.n_slabs == n_slabs

    def test_growing_q_tightens_capacity(self):
        # a later slab with larger |q| must tighten the bound retroactively
        small = np.full((1, 256), 0.25, dtype=np.float32)
        tf = TemporalField("hszx", eps=2**-16)
        tf.append(small)
        cap_small = summary_capacity(tf._q_abs_max)
        big = np.linspace(0.5, 4.0, 256, dtype=np.float32).reshape(1, 256)
        q_big = int(np.max(np.abs(np.round(big / 2**-16))))
        if tf.n_steps + 1 > summary_capacity(q_big):
            with pytest.raises(SummaryCapacityError):
                tf.append(big)
        else:
            tf.append(big)
            assert summary_capacity(tf._q_abs_max) <= cap_small

    def test_normal_streams_unaffected(self):
        rng = np.random.default_rng(7)
        tf = TemporalField("hszp", rel_eb=1e-3)
        for _ in range(4):
            tf.append(rng.normal(size=(3, 64)).astype(np.float32))
        assert tf.n_steps == 12


# ===========================================================================
# analyzer (3b): trace-time stringification + stale-waiver warnings
# ===========================================================================

_FSTRING_SYNC_FIXTURE = '''
import jax
import jax.numpy as jnp

@jax.jit
def f(x):
    s = jnp.sum(x)
    print(f"sum={s}")
    return s
'''

_STRINGIFY_FIXTURE = '''
import jax
import jax.numpy as jnp

@jax.jit
def f(x):
    s = jnp.sum(x)
    a = str(s)
    b = format(s, ".3f")
    c = "{}".format(s)
    return s
'''

_STATIC_FSTRING_FIXTURE = '''
import jax

@jax.jit
def f(x):
    print(f"shape={x.shape}")
    return x
'''

_STALE_WAIVE_FIXTURE = '''
import jax

@jax.jit
def f(x):
    return x + 1  # audit: waive(host-sync)
'''


class TestTraceStringification:
    def test_fstring_on_traced_value_one_finding(self):
        fs = tracesafety.lint_source(_FSTRING_SYNC_FIXTURE, "fix.py")
        assert [f.invariant for f in fs] == ["host-sync"]

    def test_str_format_builtins_flagged(self):
        fs = tracesafety.lint_source(_STRINGIFY_FIXTURE, "fix.py")
        assert [f.invariant for f in fs] == ["host-sync"] * 3

    def test_static_fstring_not_flagged(self):
        assert tracesafety.lint_source(_STATIC_FSTRING_FIXTURE,
                                       "fix.py") == []

    def test_stale_waiver_is_warning_not_error(self):
        fs = tracesafety.lint_source(_STALE_WAIVE_FIXTURE, "fix.py")
        assert [(f.invariant, f.severity) for f in fs] \
            == [("stale-waiver", "warning")]
        rep = AuditReport(findings=fs)
        assert rep.ok and rep.warnings and not rep.errors


# ===========================================================================
# analyzer (4b): kernel-mode keys, covers predicates, stale invariants
# ===========================================================================

_UNCOVERED_DISPATCH_FIXTURE = '''
class FusedRule:
    pass

def _covers_bad(ctx):
    return ctx.scheme.is_lorenzo and ctx.eps_budget > 0

RULES = {"d": FusedRule(lambda c, a: None, _covers_bad)}
'''


class TestJitKeyKernelMode:
    def _engine_source(self):
        from pathlib import Path

        import repro

        return (Path(repro.__file__).parent / "analytics"
                / "engine.py").read_text()

    def test_kernel_sig_dropped_from_batch_key_one_finding(self):
        engine = self._engine_source()
        sab = engine.replace("seed_sig, oplib.kernel_sig())", "seed_sig)")
        assert sab != engine
        fs = jitkeys.analyze_source(sab, "engine.py")
        assert [(f.invariant, f.subject) for f in fs] \
            == [("unkeyed-kernel-mode", "_compiled")]

    def test_kernel_sig_dropped_from_inline_key_detected(self):
        engine = self._engine_source()
        sab = engine.replace("len(padded), oplib.kernel_sig())",
                             "len(padded))")
        assert sab != engine
        fs = jitkeys.analyze_source(sab, "engine.py")
        assert [f.invariant for f in fs] == ["unkeyed-kernel-mode"]
        assert fs[0].subject == "summarize"

    def test_covers_predicate_unkeyed_input_one_finding(self):
        fs = jitkeys.analyze_covers_source(_UNCOVERED_DISPATCH_FIXTURE,
                                           "fused.py")
        assert [(f.invariant, f.subject) for f in fs] \
            == [("uncovered-dispatch-input", "eps_budget")]

    def test_covers_predicate_helper_forwarding_followed(self):
        src = _UNCOVERED_DISPATCH_FIXTURE.replace(
            "def _covers_bad(ctx):\n"
            "    return ctx.scheme.is_lorenzo and ctx.eps_budget > 0",
            "def _helper(c):\n"
            "    return c.eps_budget > 0\n\n"
            "def _covers_bad(ctx):\n"
            "    return ctx.scheme.is_lorenzo and _helper(ctx)")
        fs = jitkeys.analyze_covers_source(src, "fused.py")
        assert [f.subject for f in fs] == ["eps_budget"]

    def test_live_covers_predicates_clean(self):
        from pathlib import Path

        import repro

        src = (Path(repro.__file__).parent / "core" / "fused.py").read_text()
        assert jitkeys.analyze_covers_source(src, "core/fused.py") == []

    def test_stale_invariant_declaration_is_warning(self):
        stale = '''
import jax

def build(cache, key):
    def run(x):
        return x + 1
    fn = jax.jit(run)  # audit: invariant(cost_model)
    cache._jitted[key] = fn
    return fn
'''
        fs = jitkeys.analyze_source(stale, "m.py")
        assert [(f.invariant, f.subject, f.severity) for f in fs] \
            == [("stale-waiver", "cost_model", "warning")]

    def test_consumed_invariant_declaration_not_stale(self):
        used = '''
import jax

def build(cache, key, cost_model):
    def run(x):
        return x + cost_model.weight
    fn = jax.jit(run)  # audit: invariant(cost_model)
    cache._jitted[key] = fn
    return fn
'''
        assert jitkeys.analyze_source(used, "m.py") == []


# ===========================================================================
# analyzer (5): kernel symbolic verifier (kernelspec)
# ===========================================================================

_SPEC = next(s for s in KERNEL_SPECS if s.name == "fused.lorenzo2d")

_FMA_FIXTURE = '''
import jax.numpy as jnp

def _kern(q_ref, eps_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * eps_ref[0]
'''


class TestKernelSpecAnalyzer:
    def test_live_kernel_layer_clean(self):
        assert kernelspec.analyze_kernel_specs() == []

    def test_every_pallas_site_has_a_spec(self):
        names = {s.name for s in KERNEL_SPECS}
        assert {"fused.lorenzo2d", "bitpack.pack", "stencil_dq.grad2d",
                "stencil_dq.laplacian2d",
                "quant_lorenzo.quant_lorenzo2d"} <= names

    def test_widened_halo_one_finding(self):
        # dropping the last-band guard lets (b+1)*r run past n0
        bad = replace(_SPEC, halos=(HaloRead("p", "(b + 1)*r", "n0"),))
        fs = kernelspec.check_spec(bad)
        assert [f.invariant for f in fs] == ["halo-out-of-bounds"]

    def test_overlapping_grid_writes_one_finding(self):
        # constant output index map: every grid step rewrites block (0, 0)
        out = TileSpec("plane", ("r", "n1"), ("0", "0"), ("n0", "n1"))
        fs = kernelspec.check_spec(replace(_SPEC, outputs=(out,)))
        assert [f.invariant for f in fs] == ["grid-write-overlap"]

    def test_coverage_gap_one_finding(self):
        # one band more of rows than the grid writes
        facts = ("n0 == nb*r + r",) + _SPEC.facts[1:]
        fs = kernelspec.check_spec(replace(_SPEC, facts=facts))
        assert [f.invariant for f in fs] == ["grid-write-gap"]

    def test_vmem_budget_one_finding(self):
        env = intwidth.Envelope(max_field_elems=2**23)  # 9F*4B >> 16 MiB
        fs = kernelspec.check_spec(_SPEC, env)
        assert [f.invariant for f in fs] == ["vmem-budget"]

    def test_block_tiling_one_finding(self):
        # a one-row halo block over an (nb, n1) array: in bounds and fully
        # covered, but off the (8, 128) tiling — the block Mosaic refused
        row = TileSpec("halo", ("1", "n1"), ("b", "0"), ("nb", "n1"))
        fs = kernelspec.check_spec(replace(_SPEC, inputs=(_SPEC.inputs[0],
                                                          row)))
        assert [f.invariant for f in fs] == ["block-tiling"]

    @pytest.mark.parametrize("change", ["no_padding", "block_index"])
    def test_element_window_escape_one_finding(self, change):
        # the vector kernel's (k+2, r+8, n2) halo windows: without the 8
        # rows of padding the last band's window runs past the plane, and
        # read as block indices its offsets land k+2 planes apart
        spec = next(s for s in KERNEL_SPECS if s.name == "fused.vector3d_q")
        assert kernelspec.check_spec(spec) == []
        eps, u, *rest = spec.inputs
        if change == "no_padding":
            u = replace(u, extent=(u.extent[0], "nb*r", u.extent[2]))
        else:
            u = replace(u, element=False)
        fs = kernelspec.check_spec(replace(spec, inputs=(eps, u, *rest)))
        assert [f.invariant for f in fs] == ["tile-out-of-bounds"]

    def test_unpack_lemma_pins_word_window_slack(self):
        assert kernelspec.check_unpack_lemma(2) == []
        fs = kernelspec.check_unpack_lemma(1)
        assert [f.invariant for f in fs] == ["unpack-oob"]

    def test_output_multiply_one_finding(self):
        fs, declared, used = kernelspec.lint_kernel_source(_FMA_FIXTURE,
                                                           "k.py")
        assert [f.invariant for f in fs] == ["output-multiply"]
        assert fs[0].line == 5 and not declared and not used

    def test_output_multiply_waiver_consumed(self):
        waived = _FMA_FIXTURE.replace(
            "* eps_ref[0]",
            "* eps_ref[0]  # audit: waive(output-multiply)")
        fs, declared, used = kernelspec.lint_kernel_source(waived, "k.py")
        assert fs == [] and declared and used

    def test_stencil_kernels_keep_eps_outside(self):
        """The dequantized stencils emit exact integers; the float eps tail
        lives in the wrapper (the PR 8 FMA-contraction hazard)."""
        from pathlib import Path

        import repro

        src = (Path(repro.__file__).parent / "kernels"
               / "stencil_dq.py").read_text()
        fs, _, _ = kernelspec.lint_kernel_source(src, "stencil_dq.py")
        assert fs == []
        sab = src.replace(
            "d0_ref[...] = qs_ref[...] - qn_ref[...]",
            "d0_ref[...] = (qs_ref[...] - qn_ref[...])"
            ".astype(jnp.float32) * 0.5")
        assert sab != src
        fs, _, _ = kernelspec.lint_kernel_source(sab, "stencil_dq.py")
        assert [f.invariant for f in fs] == ["output-multiply"]

    def test_undeclared_site_and_stale_spec(self, tmp_path):
        kdir = tmp_path / "kernels"
        kdir.mkdir()
        (kdir / "mystery.py").write_text(
            "import jax\n"
            "from jax.experimental import pallas as pl\n"
            "def go(x):\n"
            "    return pl.pallas_call(\n"
            "        lambda x_ref, o_ref: None,\n"
            "        grid=(4,),\n"
            "        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)\n")
        fs = kernelspec.analyze_kernel_specs(specs=(), src_root=tmp_path)
        assert [f.invariant for f in fs] == ["undeclared-kernel"]
        fs = kernelspec.analyze_kernel_specs(specs=(_SPEC,),
                                             src_root=tmp_path)
        assert sorted(f.invariant for f in fs) \
            == ["stale-kernel-spec", "undeclared-kernel"]

    def test_stale_kernel_waiver_warning(self, tmp_path):
        kdir = tmp_path / "kernels"
        kdir.mkdir()
        (kdir / "clean.py").write_text(
            "def _kern(q_ref, o_ref):\n"
            "    # audit: waive(output-multiply)\n"
            "    o_ref[...] = q_ref[...] + 1\n")
        fs = kernelspec.analyze_kernel_specs(specs=(), src_root=tmp_path)
        assert [(f.invariant, f.severity) for f in fs] \
            == [("stale-waiver", "warning")]


# ===========================================================================
# analyzer (6): shard-partition exactness (sharddisjoint)
# ===========================================================================

class TestShardDisjointAnalyzer:
    def test_live_shard_layer_clean(self):
        assert sharddisjoint.analyze_shard_disjoint() == []

    def test_double_owned_word_one_finding(self):
        class DoubleOwned(BlockPlacement):
            def shard_word_index(self, bits):
                stripes = super().shard_word_index(bits)
                if self.n_shards >= 2 and len(stripes[0]):
                    stripes[1] = np.unique(np.concatenate(
                        [np.asarray(stripes[1]),
                         np.asarray(stripes[0][:1])]))
                return stripes

        fs = sharddisjoint.analyze_shard_disjoint(placement_cls=DoubleOwned)
        assert [f.invariant for f in fs] == ["word-owner-overlap"]

    def test_scatter_overlap_one_finding(self):
        def overlap_routing(n_shards, placement, bits, word_idx):
            src, dst = shard_exec.gather_routing(n_shards, placement, bits,
                                                 word_idx)
            src, dst = np.array(src), np.array(dst)
            if n_shards >= 2:
                l0 = np.nonzero(dst[0] != len(word_idx))[0]
                l1 = np.nonzero(dst[1] != len(word_idx))[0]
                if l0.size and l1.size:
                    dst[1, l1[0]] = dst[0, l0[0]]
            return src, dst

        fs = sharddisjoint.analyze_shard_disjoint(routing_fn=overlap_routing)
        assert [f.invariant for f in fs] == ["scatter-overlap"]

    def test_world_scaled_sumsq_overflow_one_finding(self):
        # 129 slab steps overflow int32 Σq² once any band fans in — the
        # envelope-driven acceptance fixture for the world-size sweep
        env = intwidth.Envelope(max_slab_steps=129)
        fs = sharddisjoint.analyze_shard_disjoint(env)
        assert [f.invariant for f in fs] == ["world-sumsq-overflow"]

    def test_collective_bit_budget_overflow_one_finding(self):
        fs = sharddisjoint.analyze_shard_disjoint(
            bit_budget_fn=lambda world, container_bits=16: 15)
        assert [f.invariant for f in fs] == ["collective-overflow"]

    def test_duplicated_band_detected(self):
        def dup_bands(field, placement, region=None):
            bands = shard_exec.spatial_bands(field, placement, region)
            return bands + bands[:1] if len(bands) > 1 else bands

        fs = sharddisjoint.analyze_shard_disjoint(bands_fn=dup_bands)
        assert fs and fs[0].invariant == "band-overlap"

    def test_safe_size_table_shape(self):
        table = sharddisjoint.shard_safe_size_table()
        per = table["per_world"]
        assert per["1"]["summary_capacity_if_accumulating"] == 128
        caps = [per[str(w)]["summary_capacity_if_accumulating"]
                for w in (1, 2, 4, 8)]
        assert caps == sorted(caps, reverse=True)
        # disjoint capacity is world-independent — the proven property
        assert len({per[k]["summary_capacity_disjoint"]
                    for k in per}) == 1
        for k in per:
            assert per[k]["collective_worst_psum"] <= PSUM_CONTAINER_MAX

    def test_worst_case_psum_stays_in_container(self):
        for w in (1, 2, 3, 4, 8, 64, 1024, 4096):
            assert worst_case_psum(w) <= PSUM_CONTAINER_MAX


# ===========================================================================
# runner: --only, schema version, exit codes, both kernel modes
# ===========================================================================

class TestRunnerContract:
    def test_six_analyzers_registered(self):
        assert runner.ALL_ANALYZERS == ("registry", "intwidth", "trace",
                                        "jitkey", "kernelspec",
                                        "sharddisjoint")
        assert audit.ALL_ANALYZERS == runner.ALL_ANALYZERS

    def test_only_flag_and_schema_version(self, tmp_path, capsys):
        import json

        out = tmp_path / "AUDIT.json"
        rc = runner.main(["--only", "kernelspec,sharddisjoint",
                          "--json", str(out)])
        assert rc == 0
        data = json.loads(out.read_text())
        assert data["schema_version"] == SCHEMA_VERSION == 2
        assert data["ok"] and data["shard_safe_sizes"]["per_world"]

    def test_only_rejects_unknown_analyzer(self, capsys):
        with pytest.raises(SystemExit) as exc:
            runner.main(["--only", "nosuch"])
        assert exc.value.code == 2

    def test_exit_zero_on_warnings_only(self):
        rep = AuditReport(findings=[Finding(
            "trace", "stale-waiver", "m", severity="warning")])
        assert rep.ok and not rep.errors and len(rep.warnings) == 1
        d = rep.to_dict()
        assert d["ok"] and d["n_warnings"] == 1 and d["n_errors"] == 0

    def test_self_audit_clean_in_both_kernel_modes(self):
        for mode in ("interpret", "off"):
            with kops.override_mode(mode):
                report = audit.run_audit()
            assert report.ok, (mode, [f.render() for f in report.findings])
            assert not report.warnings
            assert report.shard_safe_sizes["per_world"]
