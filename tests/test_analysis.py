"""repro.analysis: the static plan-verifier + AST lint gate.

Covers the acceptance contract of the subsystem:
- clean run: current plans (all topologies x fp32/quant) verify with
  ZERO findings, and the repo's own sources lint clean;
- seeded defects: every mutation of a good plan (non-finite params,
  broken IO chain, inflated/stale working sets, tampered edge plan,
  dropped donation, dtype drift, host callback) and every planted lint
  hazard is reported with the RIGHT invariant/rule ID;
- the serving engine's probe (``check_plan`` / demotion records) cites
  the same registry IDs.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.ast_lint import lint_source
from repro.analysis.findings import Finding, has_errors
from repro.analysis.invariants import REGISTRY, SCOPES
from repro.analysis.jaxpr_utils import count_primitive
from repro.analysis.verify import make_pipeline_probe, verify_plan
from repro.core.dhm.compiler import PlanCheckError, QuantSpec, compile_dhm
from repro.core.dhm.pipeline import StageIOSpec
from repro.models.cnn import ALL_TOPOLOGIES, CHAIN_TOPOLOGIES, LENET5, init_cnn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_two_devices = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="pipeline-scope probes need a stage mesh "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


def _plan(topo=LENET5, **kw):
    params = init_cnn(jax.random.PRNGKey(0), topo)
    return compile_dhm(topo, params, **kw)


def _ids(findings):
    return sorted({f.rule for f in findings})


def _replace_group(plan, gi, **changes):
    """A copy of ``plan`` with fusion group ``gi`` mutated."""
    flat = list(plan.fusion_groups)
    flat[gi] = dataclasses.replace(flat[gi], **changes)
    stages, k = [], 0
    for st in plan.stages:
        n = len(st.groups)
        stages.append(
            dataclasses.replace(st, groups=tuple(flat[k:k + n]))
        )
        k += n
    return dataclasses.replace(plan, stages=tuple(stages))


# ---------------------------------------------------------------------------
# registry hygiene


class TestRegistry:
    def test_ids_are_unique_and_scoped(self):
        assert len(REGISTRY) == len({inv.id for inv in REGISTRY.values()})
        for inv in REGISTRY.values():
            assert inv.scope in SCOPES
            assert inv.doc, f"{inv.id} has no doc"

    def test_expected_invariants_present(self):
        want = {
            "V001", "V002", "V003", "V004", "V005", "V006", "V007", "V008",
            "V101", "V102", "V103",
            "V201", "V202", "V203", "V204",
            "V301", "V302", "V303", "V304", "V305",
        }
        assert want <= set(REGISTRY)

    def test_finding_severity_validated(self):
        with pytest.raises(ValueError):
            Finding(rule="X", name="x", severity="fatal", message="m")


# ---------------------------------------------------------------------------
# clean runs


class TestCleanRun:
    def test_lenet5_verifies_clean(self):
        plan = _plan()
        assert verify_plan(
            plan, scopes=("plan", "structure", "resource")
        ) == []

    def test_interpret_probe_verifies_clean(self):
        plan = _plan(quant=QuantSpec(weight_bits=3, act_bits=3),
                     backend="pallas_interpret")
        assert verify_plan(
            plan, ids=("V001", "V002", "V003", "V007", "V203")
        ) == []

    def test_int8_plan_verifies_clean(self):
        """True-int8 plans pass the full single-device registry, including
        the integer-compute (V008) and int8-slab-costing (V204) gates."""
        plan = _plan(
            quant=QuantSpec(weight_bits=8, act_bits=8, int8_compute=True)
        )
        assert verify_plan(
            plan, scopes=("plan", "structure", "resource")
        ) == []

    def test_int8_interpret_probe_verifies_clean(self):
        """On the interpret probe the int8 plan keeps one in-kernel quant
        round per layer plus exactly one host-side input-quantize round
        per fusion group (the V007 int8 accounting), integer pallas-body
        dots (V008) and int8 traced footprints under the int8 costing
        (V203/V204)."""
        plan = _plan(
            quant=QuantSpec(weight_bits=8, act_bits=8, int8_compute=True),
            backend="pallas_interpret",
        )
        assert verify_plan(
            plan,
            ids=("V001", "V002", "V003", "V007", "V008", "V203", "V204"),
        ) == []

    @pytest.mark.slow
    def test_all_topologies_fp32_and_quant_verify_clean(self):
        """The acceptance matrix: five topologies x fp32/quant, zero
        findings (single-device artifacts; the pipelined closures get
        the same treatment in the CLI and the mesh-gated test below)."""
        for name, topo in CHAIN_TOPOLOGIES.items():
            params = init_cnn(jax.random.PRNGKey(0), topo)
            for quant in (QuantSpec(), QuantSpec(weight_bits=6, act_bits=6)):
                plan = compile_dhm(topo, params, quant=quant)
                assert verify_plan(
                    plan, scopes=("plan", "structure", "resource")
                ) == [], f"{name}/{quant}"

    @needs_two_devices
    def test_pipelined_closure_verifies_clean(self):
        plan = _plan(n_stages=2)
        probe = make_pipeline_probe(plan, microbatch=2)
        assert probe.edge_plan.mode == "exact"
        assert verify_plan(
            plan, scopes=("pipeline",), pipeline=probe
        ) == []

    def test_repo_sources_lint_clean(self):
        from repro.analysis.cli import run_lint

        assert run_lint() == []


# ---------------------------------------------------------------------------
# seeded plan defects -> named invariant IDs


class TestSeededPlanDefects:
    def test_nonfinite_param_is_V301(self):
        plan = _plan()
        bad_params = tuple(
            {k: (v.at[0].set(jnp.nan) if k == "b" else v)
             for k, v in p.items()} if i == 0 else p
            for i, p in enumerate(plan.conv_params)
        )
        bad = dataclasses.replace(plan, conv_params=bad_params)
        assert _ids(
            [f for f in verify_plan(bad, scopes=("plan",)) if f.is_error]
        ) == ["V301"]
        with pytest.raises(PlanCheckError) as ei:
            bad.self_check()
        assert ei.value.invariants == ("V301",)

    def test_broken_io_chain_is_V302(self):
        plan = _plan(n_stages=2)
        st0 = plan.stages[0]
        bad_io = StageIOSpec(
            in_shape=st0.io.in_shape, out_shape=(1, 1, 999)
        )
        bad = dataclasses.replace(
            plan,
            stages=(dataclasses.replace(st0, io=bad_io),) + plan.stages[1:],
        )
        ids = _ids(verify_plan(bad, scopes=("plan",)))
        assert "V302" in ids  # the chain breaks at the tampered edge
        assert "V303" in ids  # and the stage body contradicts its spec

    def test_inflated_working_set_is_V201_V202(self):
        plan = _plan()
        bad = _replace_group(plan, 0, working_set=10**9)
        assert _ids(verify_plan(bad, scopes=("resource",))) == [
            "V201", "V202"
        ]
        # the V202 message names the dominant cost component
        msgs = [
            f.message for f in verify_plan(bad, scopes=("resource",))
            if f.rule == "V202"
        ]
        assert any("largest component" in m for m in msgs)

    def test_underestimated_working_set_is_V203(self):
        plan = _plan(backend="pallas_interpret")
        bad = _replace_group(plan, 0, working_set=1)
        ids = _ids(verify_plan(bad, scopes=("resource",)))
        assert "V203" in ids  # traced footprint exceeds the recorded cost
        assert "V202" in ids  # and the cost model disagrees too

    def test_fp32_compute_under_int8_contract_is_V008(self):
        """Seeded defect: a plan whose kernels matmul in fp32 (the
        fake-quant lowering) but whose QuantSpec claims int8_compute —
        the hidden-upcast class V008 exists to catch."""
        fq = _plan(quant=QuantSpec(weight_bits=8, act_bits=8))
        lying = dataclasses.replace(
            fq, quant=QuantSpec(weight_bits=8, act_bits=8, int8_compute=True)
        )
        findings = verify_plan(lying, ids=("V008",))
        assert _ids(findings) == ["V008"]
        assert any("float" in f.message for f in findings)

    def test_fp32_bytes_under_int8_contract_is_V204(self):
        """Seeded defect: an int8 plan whose fusion group books the fp32
        working set — the budget headroom the 1-byte slabs buy is
        silently wasted."""
        from repro.core.dhm.fusion import group_working_set

        plan = _plan(
            quant=QuantSpec(weight_bits=8, act_bits=8, int8_compute=True)
        )
        g = plan.fusion_groups[0]
        fp32_cost = group_working_set(
            plan.topo, g.layers, block_rows=g.block_rows, elem_bytes=4
        )
        bad = _replace_group(plan, 0, working_set=fp32_cost)
        findings = verify_plan(bad, ids=("V204",))
        assert _ids(findings) == ["V204"]
        # and the honest int8 plan is clean under the same gate
        assert verify_plan(plan, ids=("V204",)) == []

    def test_dtype_drift_is_V004(self):
        plan = _plan()
        drifted = dataclasses.replace(
            plan,
            head_fn=lambda h, _inner=plan.head_fn: _inner(
                h.astype(jnp.bfloat16).astype(jnp.float32)
            ),
        )
        assert _ids(verify_plan(drifted, ids=("V004",))) == ["V004"]

    def test_host_callback_is_V005(self):
        plan = _plan()

        def cb_head(h, _inner=plan.head_fn):
            h = jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct(h.shape, h.dtype), h
            )
            return _inner(h)

        bad = dataclasses.replace(plan, head_fn=cb_head)
        assert _ids(verify_plan(bad, ids=("V005",))) == ["V005"]

    def test_dropped_donation_is_V006(self):
        plan = _plan()

        class _DropsDonate:
            """A plan whose jitted_forward silently ignores donate=."""

            topo = plan.topo
            backend = plan.backend

            def jitted_forward(self, *, donate=False):
                return plan.jitted_forward(donate=False)

        assert _ids(verify_plan(_DropsDonate(), ids=("V006",))) == ["V006"]

    def test_good_plan_declares_donation(self):
        assert verify_plan(_plan(), ids=("V006",)) == []


@needs_two_devices
class TestSeededPipelineDefects:
    def _probe(self, plan):
        return make_pipeline_probe(plan, microbatch=2)

    def test_dropped_edge_class_is_V101(self):
        plan = _plan(n_stages=2)
        probe = self._probe(plan)
        ep = probe.edge_plan
        # claim a second shape class that no traced collective serves
        tampered = dataclasses.replace(
            ep,
            class_shapes=ep.class_shapes + ((9, 9, 9),),
            edge_class=tuple(1 for _ in ep.edge_class),
        )
        bad = dataclasses.replace(probe, edge_plan=tampered)
        ids = _ids(verify_plan(plan, scopes=("pipeline",), pipeline=bad))
        assert "V101" in ids

    def test_wrong_edge_shape_is_V102(self):
        plan = _plan(n_stages=2)
        probe = self._probe(plan)
        ep = probe.edge_plan
        tampered = dataclasses.replace(
            ep, class_shapes=((9, 9, 9),) * len(ep.class_shapes)
        )
        bad = dataclasses.replace(probe, edge_plan=tampered)
        ids = _ids(verify_plan(plan, scopes=("pipeline",), pipeline=bad))
        assert "V102" in ids

    def test_boxed_fallback_is_flagged_V103(self):
        plan = _plan(n_stages=2)
        probe = make_pipeline_probe(plan, microbatch=2, edge_mode="boxed")
        findings = verify_plan(plan, scopes=("pipeline",), pipeline=probe)
        warnings_ = [f for f in findings if f.rule == "V103"]
        assert len(warnings_) == 1
        assert not warnings_[0].is_error
        assert "padding" in warnings_[0].message


# ---------------------------------------------------------------------------
# engine integration: same registry on the serving path


class TestEngineIntegration:
    def test_check_plan_runs_plan_scope(self):
        # a good plan passes the same gate the engine probes before
        # activating a rung
        _plan().self_check()

    def test_demotion_record_cites_invariants(self):
        from repro.core.dhm.engine import Engine

        e = PlanCheckError("nope", invariants=("V301", "V303"))
        rec = Engine._demotion_record("fused", e)
        assert rec["invariants"] == ["V301", "V303"]
        assert rec["rung"] == "fused"
        # plain exceptions keep the legacy record shape
        rec = Engine._demotion_record("fused", RuntimeError("x"))
        assert "invariants" not in rec


# ---------------------------------------------------------------------------
# AST lint: seeded fixtures -> named rule IDs


_ENGINE_PATH = "src/repro/core/dhm/engine.py"
_BENCH_PATH = "benchmarks/my_bench.py"


class TestLintRules:
    def test_eager_concat_is_DHM001(self):
        src = (
            "import jax.numpy as jnp\n"
            "def flush(frames):\n"
            "    return jnp.concatenate(frames, axis=0)\n"
        )
        ids = _ids(lint_source(src, _ENGINE_PATH))
        assert ids == ["DHM001"]

    def test_numpy_pack_is_clean(self):
        src = (
            "import numpy as np\n"
            "def flush(frames):\n"
            "    return np.concatenate(frames, axis=0)\n"
        )
        assert lint_source(src, _ENGINE_PATH) == []

    def test_stack_inside_jit_is_DHM002(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "@jax.jit\n"
            "def fwd(leaves, x):\n"
            "    w = jnp.stack(leaves, axis=0)\n"
            "    return x @ w\n"
        )
        ids = _ids(lint_source(src, _ENGINE_PATH))
        assert ids == ["DHM002"]

    def test_jax_jit_by_reference_is_DHM002(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def fwd(leaves, x):\n"
            "    return x @ jnp.stack(leaves)\n"
            "fwd_j = jax.jit(fwd)\n"
        )
        ids = _ids(lint_source(src, "src/repro/core/dhm/pipeline.py"))
        assert ids == ["DHM002"]

    def test_eager_stack_outside_jit_in_pipeline_is_clean(self):
        # the PR-7 fix: box + stack EAGERLY, outside any trace
        src = (
            "import jax.numpy as jnp\n"
            "def box(params):\n"
            "    return jnp.stack(params, axis=0)\n"
        )
        assert lint_source(src, "src/repro/core/dhm/pipeline.py") == []

    def test_timing_without_block_is_DHM003(self):
        src = (
            "import time\n"
            "import jax.numpy as jnp\n"
            "def bench(a, b):\n"
            "    t0 = time.perf_counter()\n"
            "    y = jnp.dot(a, b)\n"
            "    return time.perf_counter() - t0, y\n"
        )
        ids = _ids(lint_source(src, _BENCH_PATH))
        assert ids == ["DHM003"]

    def test_timing_with_block_is_clean(self):
        src = (
            "import time\n"
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def bench(a, b):\n"
            "    t0 = time.perf_counter()\n"
            "    y = jax.block_until_ready(jnp.dot(a, b))\n"
            "    return time.perf_counter() - t0, y\n"
        )
        assert lint_source(src, _BENCH_PATH) == []

    def test_bare_except_is_DHM004(self):
        src = (
            "def drain(q):\n"
            "    try:\n"
            "        q.get()\n"
            "    except:\n"
            "        pass\n"
        )
        ids = _ids(lint_source(src, _ENGINE_PATH))
        assert ids == ["DHM004"]

    def test_swallowed_request_error_is_DHM004(self):
        src = (
            "from repro.core.dhm.engine import DeadlineExceeded\n"
            "def flush(req):\n"
            "    try:\n"
            "        req.complete()\n"
            "    except DeadlineExceeded:\n"
            "        pass\n"
        )
        ids = _ids(lint_source(src, _ENGINE_PATH))
        assert ids == ["DHM004"]

    def test_handled_request_error_is_clean(self):
        src = (
            "from repro.core.dhm.engine import DeadlineExceeded\n"
            "def flush(req):\n"
            "    try:\n"
            "        req.complete()\n"
            "    except DeadlineExceeded as e:\n"
            "        req.fail(e)\n"
        )
        assert lint_source(src, _ENGINE_PATH) == []

    def test_float64_cast_is_DHM005(self):
        src = (
            "import jax.numpy as jnp\n"
            "def widen(x):\n"
            "    return x.astype('float64') + jnp.zeros((), jnp.float64)\n"
        )
        findings = lint_source(src, "src/repro/core/dhm/anything.py")
        assert _ids(findings) == ["DHM005"]
        assert len(findings) == 2  # the astype and the jnp.float64

    def test_unbounded_background_thread_is_DHM006(self):
        # the PR-9 stop() bug class: a serving thread with no
        # timeout-bounded join leaks past interpreter shutdown
        src = (
            "import threading\n"
            "def start(loop):\n"
            "    t = threading.Thread(target=loop, daemon=True)\n"
            "    t.start()\n"
            "    return t\n"
        )
        ids = _ids(lint_source(src, _ENGINE_PATH))
        assert ids == ["DHM006"]
        assert _ids(
            lint_source(src, "src/repro/core/dhm/multitenant.py")
        ) == ["DHM006"]

    def test_bounded_join_is_clean_DHM006(self):
        src = (
            "import threading\n"
            "def start(loop):\n"
            "    t = threading.Thread(target=loop, daemon=True)\n"
            "    t.start()\n"
            "    return t\n"
            "def stop(t):\n"
            "    t.join(timeout=30.0)\n"
            "    if t.is_alive():\n"
            "        raise RuntimeError('wedged')\n"
        )
        assert lint_source(src, _ENGINE_PATH) == []

    def test_str_join_does_not_satisfy_DHM006(self):
        # '; '.join(msgs) is not a thread join — the rule must still fire
        src = (
            "import threading\n"
            "def start(loop, msgs):\n"
            "    t = threading.Thread(target=loop)\n"
            "    t.start()\n"
            "    return '; '.join(msgs)\n"
        )
        assert _ids(lint_source(src, _ENGINE_PATH)) == ["DHM006"]

    def test_rules_are_scoped_by_path(self):
        # a kernel body may stack taps eagerly — serving rules must not
        # fire outside their path scope
        src = (
            "import jax.numpy as jnp\n"
            "def kernel(taps):\n"
            "    return jnp.stack(taps, axis=2)\n"
        )
        assert lint_source(src, "src/repro/kernels/stream_conv/conv.py") == []
        # DHM006 is a serving-file rule: kernel/pipeline modules may own
        # unjoined worker threads (the watchdog does)
        src = (
            "import threading\n"
            "def watch(fn):\n"
            "    threading.Thread(target=fn, daemon=True).start()\n"
        )
        assert lint_source(src, "src/repro/core/dhm/pipeline.py") == []

    def test_findings_carry_file_and_line(self):
        src = (
            "import jax.numpy as jnp\n"
            "def flush(frames):\n"
            "    return jnp.stack(frames)\n"
        )
        (f,) = lint_source(src, _ENGINE_PATH)
        assert f.where == f"{_ENGINE_PATH}:3"
        assert has_errors([f])


# ---------------------------------------------------------------------------
# shared jaxpr helpers (the deduped _count_primitive home)


class TestJaxprUtils:
    def test_count_descends_into_pjit(self):
        f = jax.jit(lambda a, b: a @ b)
        jaxpr = jax.make_jaxpr(f)(jnp.ones((4, 4)), jnp.ones((4, 4)))
        assert count_primitive(jaxpr, "dot_general") == 1

    def test_counts_match_legacy_semantics(self):
        from repro.analysis.jaxpr_utils import count_primitive_in_pallas

        plan = _plan(quant=QuantSpec(act_bits=4), backend="pallas_interpret")
        jaxpr = jax.make_jaxpr(plan.features)(
            jnp.ones((1,) + tuple(plan.stages[0].io.in_shape))
        )
        n_conv = len(plan.topo.conv_layers)
        assert count_primitive_in_pallas(jaxpr, "round") == n_conv
        assert count_primitive(jaxpr, "round") == n_conv


# ---------------------------------------------------------------------------
# CLI


class TestCLI:
    @pytest.mark.slow
    def test_cli_clean_run_exits_zero(self, tmp_path):
        out = tmp_path / "findings.json"
        proc = subprocess.run(
            [
                sys.executable, "-m", "repro.analysis", "all",
                "--topology", "lenet5", "--format", "json",
                "--out", str(out), "--no-pipeline",
            ],
            capture_output=True, text=True, timeout=560,
            env={
                **os.environ,
                "PYTHONPATH": os.path.join(REPO, "src"),
            },
            cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        rep = json.loads(proc.stdout)
        assert rep["errors"] == 0
        assert json.loads(out.read_text())["findings"] == rep["findings"]

    def test_verify_rejects_unknown_scope(self):
        with pytest.raises(ValueError, match="unknown scopes"):
            verify_plan(_plan(), scopes=("nope",))
