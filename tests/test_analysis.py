"""repro.analysis (DESIGN.md §14): the AST lint engine over the
known-bad fixture tree, the legacy-regex blind-spot regression, and the
plan verifier's rejection of malformed plans with *named* violations.

The fixture tree (``tests/fixtures/lint/``) mirrors the repo layout so
the rules' path scoping is exercised exactly as the real gate applies
it; pytest never collects the fixtures (they are not ``test_*.py``) and
the real gate never scans ``tests/``.
"""
import dataclasses
import importlib.util
import json
import pathlib
import shutil
import types
import warnings

import jax
import numpy as np
import pytest

from repro.analysis import (LintEngine, PlanVerificationError, Severity,
                            all_rules, findings_to_json, format_findings,
                            lint_tree, rule_by_id, verify_plan)
from repro.analysis.rules import LEGACY_TIME_RE
from repro.core.quantize import QFormat, QTensor
from repro.graph.ir import (FusedConvBlockNode, Graph, QuantizeNode,
                            ShardingSpec)
from repro.models.cnn import PaperCNN, PaperCNNConfig
from repro.ops import ExecPolicy

HERE = pathlib.Path(__file__).parent
FIXTURES = HERE / "fixtures" / "lint"
KEY = jax.random.PRNGKey(0)
QUANTS = ("none", "qformat", "int8")


def fixture_findings():
    engine = LintEngine(FIXTURES)
    return engine.lint_dirs(("src/repro", "benchmarks"))


# ------------------------------------------------------------ lint rules

class TestRuleFixtures:
    """Every grep-gate violation class reproduces on the fixture tree,
    at the exact (path, line) the snippet plants it."""

    def test_every_gate_class_reproduced(self):
        hits = {(f.path, f.line, f.rule) for f in fixture_findings()}
        assert ("benchmarks/bad_dispatch.py", 5, "string-dispatch") in hits
        assert ("benchmarks/bad_dispatch.py", 6, "interpret-literal") in hits
        assert ("benchmarks/bad_chain.py", 5, "conv-chain") in hits
        assert ("benchmarks/bad_shard.py", 5, "shard-map-conv") in hits
        assert ("src/repro/util/bad_random.py", 7, "global-random") in hits
        assert ("src/repro/util/bad_random.py", 8, "global-random") in hits
        assert ("src/repro/util/bad_except.py", 7, "bare-except") in hits
        assert ("src/repro/configs/bad_default.py", 4,
                "mutable-default") in hits

    def test_raw_clock_catches_every_aliased_form(self):
        f = LintEngine(FIXTURES).lint_file(
            FIXTURES / "src/repro/serve/bad_clock.py")
        assert [(x.rule, x.line) for x in f] == \
            [("raw-clock", n) for n in (3, 4, 8, 9, 10)]

    def test_sanctioned_rng_not_flagged(self):
        f = LintEngine(FIXTURES).lint_file(
            FIXTURES / "src/repro/util/bad_random.py")
        assert all(x.line != 9 for x in f)   # RandomState(0) is allowed

    def test_exempt_clock_file_is_clean(self):
        assert LintEngine(FIXTURES).lint_file(
            FIXTURES / "src/repro/serve/clock.py") == []

    def test_suppression_lets_only_the_marked_sites_pass(self):
        f = LintEngine(FIXTURES).lint_file(
            FIXTURES / "src/repro/serve/suppressed.py")
        assert [(x.rule, x.line) for x in f] == [("raw-clock", 8)]

    def test_findings_are_structured(self):
        f = fixture_findings()
        assert f == sorted(f)                # stable order
        for x in f:
            assert x.severity is Severity.ERROR
            assert x.snippet and x.fix       # every rule suggests a fix
        doc = json.loads(findings_to_json(f))
        assert doc["errors"] == len(f) and doc["warnings"] == 0
        summary = format_findings(f, scanned=11)
        assert summary.splitlines()[-1].startswith("repro.analysis:")
        assert "across 11 files" in summary

    def test_rule_catalog_metadata(self):
        rules = all_rules()
        assert {r.id for r in rules} >= {
            "string-dispatch", "interpret-literal", "conv-chain",
            "shard-map-conv", "raw-clock", "global-random", "bare-except",
            "mutable-default"}
        for r in rules:
            assert r.doc and r.anchor.startswith("DESIGN.md")
        assert rule_by_id("raw-clock").anchor == "DESIGN.md §11"
        with pytest.raises(KeyError):
            rule_by_id("no-such-rule")

    def test_real_tree_gate_is_green(self):
        errors = [f for f in lint_tree(HERE.parent)
                  if f.severity is Severity.ERROR]
        assert errors == [], "\n".join(f.render() for f in errors)


# ----------------------------------------- legacy regex blind spots

class TestLegacyRegexBlindSpots:
    """The regression the ISSUE pins: the old ``TIME_RE`` grep missed
    aliased and from-imported clocks that the AST rule catches."""

    @staticmethod
    def _shim_regex():
        spec = importlib.util.spec_from_file_location(
            "check_dispatch_shim",
            HERE.parent / "scripts" / "check_dispatch.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.TIME_RE

    def test_regex_misses_every_line_the_ast_rule_catches(self):
        text = (FIXTURES / "src/repro/serve/bad_clock.py").read_text()
        assert not any(LEGACY_TIME_RE.search(ln)
                       for ln in text.splitlines())
        f = LintEngine(FIXTURES).lint_file(
            FIXTURES / "src/repro/serve/bad_clock.py")
        assert len([x for x in f if x.rule == "raw-clock"]) == 5

    def test_shim_preserves_the_historical_regex(self):
        shim_re = self._shim_regex()
        assert shim_re.pattern == LEGACY_TIME_RE.pattern
        assert shim_re.search("time.sleep(0.1)")     # plain form: parity
        assert not shim_re.search("t.monotonic()")   # aliased: blind
        assert not shim_re.search("monotonic()")     # from-import: blind


# ------------------------------------------------------- plan verifier

def _model():
    return PaperCNN(PaperCNNConfig())


def _replace_node(plan, node, **changes):
    """A tampered copy of ``plan`` with one node's fields replaced."""
    nodes = tuple(dataclasses.replace(n, **changes) if n.id == node.id
                  else n for n in plan.graph)
    graph = Graph(nodes=nodes, input_id=plan.graph.input_id,
                  output_id=plan.graph.output_id)
    return dataclasses.replace(plan, graph=graph)


class TestVerifyPlan:
    def test_clean_plans_verify_for_every_quant(self):
        params = _model().init(KEY)
        for q in QUANTS:
            plan = _model().compile(ExecPolicy(quant=q))
            assert verify_plan(plan) == []
            assert verify_plan(plan.bind(params, verify=False)) == []

    def test_verification_is_read_only(self):
        from repro.artifact.fingerprint import plan_fingerprint
        for kw in ({}, {"batch": 2}):
            a = _model().compile(verify=False, **kw)
            b = _model().compile(verify=True, **kw)
            assert plan_fingerprint(a) == plan_fingerprint(b)

    def test_non_divisible_icp_named(self):
        plan = _model().compile()
        conv1 = next(n for n in plan.graph
                     if isinstance(n, FusedConvBlockNode))
        bad = _replace_node(plan, conv1,
                            sharding=ShardingSpec(mode="input", data=False))
        # 2-wide model axis the 1-channel MNIST input cannot divide
        # (in-process tests see one device, so stub the mesh's identity)
        bad = dataclasses.replace(bad, mesh=types.SimpleNamespace(
            axis_names=("model",), shape={"model": 2},
            devices=np.zeros((2,))))
        codes = [v.code for v in
                 verify_plan(bad, raise_on_violation=False)]
        assert "shard-divisibility" in codes

    def test_2d_factorization_mismatch_named(self):
        """Explicit icp x ocp factors that don't cover the model axis."""
        plan = _model().compile()
        conv1 = next(n for n in plan.graph
                     if isinstance(n, FusedConvBlockNode))
        bad = _replace_node(plan, conv1,
                            sharding=ShardingSpec(mode="both", data=False,
                                                  icp=2, ocp=2))
        bad = dataclasses.replace(bad, mesh=types.SimpleNamespace(
            axis_names=("model",), shape={"model": 2},
            devices=np.zeros((2,))))
        codes = [v.code for v in
                 verify_plan(bad, raise_on_violation=False)]
        assert "shard-factorization" in codes

    def test_2d_both_axis_divisibility_named(self):
        """A 'both' split must divide N by icp AND M by ocp — conv1
        (M=15, N=1) at icp=2 x ocp=2 violates both sides."""
        plan = _model().compile()
        conv1 = next(n for n in plan.graph
                     if isinstance(n, FusedConvBlockNode))
        bad = _replace_node(plan, conv1,
                            sharding=ShardingSpec(mode="both", data=False,
                                                  icp=2, ocp=2))
        bad = dataclasses.replace(bad, mesh=types.SimpleNamespace(
            axis_names=("model",), shape={"model": 4},
            devices=np.zeros((4,))))
        violations = verify_plan(bad, raise_on_violation=False)
        div = [v for v in violations if v.code == "shard-divisibility"]
        assert len(div) == 2, violations
        assert any("Eq. 7/ICP" in v.message for v in div)
        assert any("Eq. 6/OCP" in v.message for v in div)

    def test_pure_data_stage_with_model_factors_named(self):
        """mode=none with leftover icp/ocp factors claims a collective
        the executor never runs — rejected even without a mesh."""
        plan = _model().compile()
        conv1 = next(n for n in plan.graph
                     if isinstance(n, FusedConvBlockNode))
        bad = _replace_node(plan, conv1,
                            sharding=ShardingSpec(mode="none", icp=2,
                                                  ocp=1))
        codes = [v.code for v in
                 verify_plan(bad, raise_on_violation=False)]
        assert "shard-pure-data-collective" in codes

    def test_gather_moving_batch_axis_named(self):
        """A model-sharded stage with data=False feeding the flatten on a
        mesh WITH a data axis: the gather would reshard the batch dim,
        not just all-gather the model axis."""
        plan = _model().compile()
        conv2 = [n for n in plan.graph
                 if isinstance(n, FusedConvBlockNode)][-1]
        bad = _replace_node(plan, conv2,
                            sharding=ShardingSpec(mode="output",
                                                  data=False))
        bad = dataclasses.replace(bad, mesh=types.SimpleNamespace(
            axis_names=("data", "model"), shape={"data": 2, "model": 5},
            devices=np.zeros((2, 5))))
        codes = [v.code for v in
                 verify_plan(bad, raise_on_violation=False)]
        assert "shard-gather-axis" in codes

    def test_sharded_stage_without_mesh_named(self):
        plan = _model().compile()
        conv1 = next(n for n in plan.graph
                     if isinstance(n, FusedConvBlockNode))
        bad = _replace_node(plan, conv1,
                            sharding=ShardingSpec(mode="output"))
        with pytest.raises(PlanVerificationError) as e:
            verify_plan(bad)
        assert any(v.code == "shard-mesh" for v in e.value.violations)

    def test_qtensor_scale_mismatch_named(self):
        model = _model()
        bound = model.compile(ExecPolicy(quant="int8")).bind(
            model.init(KEY), verify=False)
        nid, val = next(
            (n.id, bound.folded[n.id]) for n in bound.plan.graph
            if isinstance(n, QuantizeNode)
            and n.kind == "int8_conv_weight" and n.id in bound.folded)
        assert isinstance(val, QTensor)
        bound.folded[nid] = QTensor(codes=val.codes,
                                    scale=val.scale.reshape(-1)[:1])
        with pytest.raises(PlanVerificationError) as e:
            verify_plan(bound)
        assert any(v.code == "quant-scale-shape"
                   for v in e.value.violations)

    def test_fp_weight_reaching_int8_stage_named(self):
        plan = _model().compile(ExecPolicy(quant="int8"))
        conv = next(n for n in plan.graph
                    if isinstance(n, FusedConvBlockNode))
        # rewire the weight edge past its quantize node: an fp ParamRef
        # would flow straight into the int8 kernel
        wq = plan.graph.node(conv.inputs[1])
        assert isinstance(wq, QuantizeNode)
        bad = _replace_node(plan, conv,
                            inputs=(conv.inputs[0], wq.inputs[0] if
                                    wq.inputs else conv.inputs[0]))
        codes = [v.code for v in
                 verify_plan(bad, raise_on_violation=False)]
        assert "quant-weight-unlowered" in codes

    def test_violations_render_named_not_stack_traces(self):
        plan = _model().compile()
        conv1 = next(n for n in plan.graph
                     if isinstance(n, FusedConvBlockNode))
        bad = _replace_node(plan, conv1,
                            sharding=ShardingSpec(mode="output"))
        with pytest.raises(PlanVerificationError) as e:
            verify_plan(bad)
        msg = str(e.value)
        assert "shard-mesh" in msg and f"%{conv1.id}" in msg
        assert "violation" in msg


# ------------------------------------------------- wiring + artifacts

class TestVerifierWiring:
    def test_compile_verify_kwarg_default_on(self, monkeypatch):
        calls = []
        import repro.analysis.verifier as V
        real = V.verify_plan
        monkeypatch.setattr(V, "verify_plan",
                            lambda p, **kw: calls.append(p) or real(p, **kw))
        _model().compile()
        assert len(calls) == 1
        _model().compile(verify=False)
        assert len(calls) == 1

    def test_tampered_artifact_rejected_with_named_violation(self, tmp_path):
        from repro.artifact import PlanStore
        from repro.artifact.fingerprint import (plan_fingerprint,
                                                policy_from_doc)
        from repro.artifact.ir_codec import graph_from_doc
        from repro.artifact.store import ArtifactError, load_plan
        from repro.graph.plan import ExecutionPlan

        model = _model()
        params = model.init(KEY)
        bound = model.compile(batch=2).bind(params)
        bound.save(tmp_path / "good", input_shapes=[(2, 1, 28, 28)])
        shutil.copytree(tmp_path / "good", tmp_path / "evil")

        mf = tmp_path / "evil" / "manifest.json"
        manifest = json.loads(mf.read_text())
        node_doc = next(n for n in manifest["graph"]["nodes"]
                        if n["op"] == "fused_conv_block")
        node_doc["stride"] = [2, 2]          # shapes no longer flow
        # recompute the fingerprint so the integrity check passes and
        # ONLY the verifier can catch the tamper
        plan = ExecutionPlan(
            graph=graph_from_doc(manifest["graph"]),
            quant=manifest["quant"],
            qformat=QFormat(*manifest["qformat"]),
            compile_policy=policy_from_doc(manifest["compile_policy"]),
            mesh=None)
        manifest["fingerprint"] = plan_fingerprint(
            plan, params=params, tuned={},
            bind_policy=policy_from_doc(manifest["bind_policy"]))
        mf.write_text(json.dumps(manifest))

        with pytest.raises(ArtifactError, match="static verification"):
            load_plan(tmp_path / "evil")
        with pytest.raises(ArtifactError, match="shape-flow"):
            load_plan(tmp_path / "evil")

        store = PlanStore(tmp_path)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert store.load("evil") is None
        assert any("falling back" in str(x.message) for x in w)
        # the untampered sibling still loads
        assert store.load("good") is not None
