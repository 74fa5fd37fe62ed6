"""BayesianNetworkModel (discrete, fully-observed Markov blanket):
compiled vs oracle vs hand-computed posterior on the classic
rain/sprinkler/grass network."""

import numpy as np
import pytest

from flink_jpmml_tpu.compile import compile_pmml
from flink_jpmml_tpu.pmml import parse_pmml
from flink_jpmml_tpu.pmml.interp import evaluate
from flink_jpmml_tpu.utils.exceptions import ModelLoadingException

BN = """<PMML version="4.3"><DataDictionary>
  <DataField name="rain" optype="categorical" dataType="string">
    <Value value="yes"/><Value value="no"/></DataField>
  <DataField name="sprinkler" optype="categorical" dataType="string">
    <Value value="on"/><Value value="off"/></DataField>
  <DataField name="grass" optype="categorical" dataType="string">
    <Value value="wet"/><Value value="dry"/></DataField>
  </DataDictionary>
  <BayesianNetworkModel functionName="classification">
  <MiningSchema><MiningField name="rain" usageType="target"/>
    <MiningField name="sprinkler"/><MiningField name="grass"/></MiningSchema>
  <BayesianNetworkNodes>
    <DiscreteNode name="rain">
      <ValueProbability value="yes" probability="0.2"/>
      <ValueProbability value="no" probability="0.8"/>
    </DiscreteNode>
    <DiscreteNode name="sprinkler">
      <DiscreteConditionalProbability>
        <ParentValue parent="rain" value="yes"/>
        <ValueProbability value="on" probability="0.01"/>
        <ValueProbability value="off" probability="0.99"/>
      </DiscreteConditionalProbability>
      <DiscreteConditionalProbability>
        <ParentValue parent="rain" value="no"/>
        <ValueProbability value="on" probability="0.4"/>
        <ValueProbability value="off" probability="0.6"/>
      </DiscreteConditionalProbability>
    </DiscreteNode>
    <DiscreteNode name="grass">
      <DiscreteConditionalProbability>
        <ParentValue parent="sprinkler" value="on"/>
        <ParentValue parent="rain" value="yes"/>
        <ValueProbability value="wet" probability="0.99"/>
        <ValueProbability value="dry" probability="0.01"/>
      </DiscreteConditionalProbability>
      <DiscreteConditionalProbability>
        <ParentValue parent="sprinkler" value="on"/>
        <ParentValue parent="rain" value="no"/>
        <ValueProbability value="wet" probability="0.9"/>
        <ValueProbability value="dry" probability="0.1"/>
      </DiscreteConditionalProbability>
      <DiscreteConditionalProbability>
        <ParentValue parent="sprinkler" value="off"/>
        <ParentValue parent="rain" value="yes"/>
        <ValueProbability value="wet" probability="0.8"/>
        <ValueProbability value="dry" probability="0.2"/>
      </DiscreteConditionalProbability>
      <DiscreteConditionalProbability>
        <ParentValue parent="sprinkler" value="off"/>
        <ParentValue parent="rain" value="no"/>
        <ValueProbability value="wet" probability="0.0"/>
        <ValueProbability value="dry" probability="1.0"/>
      </DiscreteConditionalProbability>
    </DiscreteNode>
  </BayesianNetworkNodes>
  </BayesianNetworkModel></PMML>"""


def _hand_posterior(sprinkler, grass):
    p_spr = {"yes": {"on": 0.01, "off": 0.99}, "no": {"on": 0.4, "off": 0.6}}
    p_grass = {
        ("on", "yes"): {"wet": 0.99, "dry": 0.01},
        ("on", "no"): {"wet": 0.9, "dry": 0.1},
        ("off", "yes"): {"wet": 0.8, "dry": 0.2},
        ("off", "no"): {"wet": 0.0, "dry": 1.0},
    }
    prior = {"yes": 0.2, "no": 0.8}
    score = {
        s: prior[s] * p_spr[s][sprinkler] * p_grass[(sprinkler, s)][grass]
        for s in ("yes", "no")
    }
    z = sum(score.values())
    return {s: v / z for s, v in score.items()}


class TestBayesianNetwork:
    def test_posterior_parity_all_evidence(self):
        doc = parse_pmml(BN)
        cm = compile_pmml(doc)
        for sprinkler in ("on", "off"):
            for grass in ("wet", "dry"):
                rec = {"sprinkler": sprinkler, "grass": grass}
                hand = _hand_posterior(sprinkler, grass)
                o = evaluate(doc, rec)
                assert o.probabilities["yes"] == pytest.approx(
                    hand["yes"], rel=1e-9
                )
                p = cm.score_records([rec])[0]
                win = max(hand, key=hand.get)
                assert o.label == win and p.target.label == win
                assert p.target.probabilities["yes"] == pytest.approx(
                    hand["yes"], rel=1e-4
                )
                assert p.score.value == pytest.approx(hand[win], rel=1e-4)

    def test_zero_probability_state(self):
        # sprinkler=off, grass=wet: P(wet|off,no)=0 kills rain=no entirely
        doc = parse_pmml(BN)
        cm = compile_pmml(doc)
        rec = {"sprinkler": "off", "grass": "wet"}
        p = cm.score_records([rec])[0]
        assert p.target.label == "yes"
        assert p.target.probabilities["no"] == pytest.approx(0.0, abs=1e-6)

    def test_impossible_evidence_empty_both_paths(self):
        # P(wet | off, yes) = 0 AND P(wet | off, no) = 0: the evidence is
        # impossible under every target state — oracle and compiled must
        # BOTH score an empty lane, not a softmax of log-clamp residue
        xml = BN.replace(
            '<ParentValue parent="sprinkler" value="off"/>\n        '
            '<ParentValue parent="rain" value="yes"/>\n        '
            '<ValueProbability value="wet" probability="0.8"/>\n        '
            '<ValueProbability value="dry" probability="0.2"/>',
            '<ParentValue parent="sprinkler" value="off"/>\n        '
            '<ParentValue parent="rain" value="yes"/>\n        '
            '<ValueProbability value="wet" probability="0.0"/>\n        '
            '<ValueProbability value="dry" probability="1.0"/>',
        )
        doc = parse_pmml(xml)
        cm = compile_pmml(doc)
        rec = {"sprinkler": "off", "grass": "wet"}
        assert evaluate(doc, rec).value is None
        assert cm.score_records([rec])[0].is_empty
        # and the possible combos still score
        ok = {"sprinkler": "on", "grass": "wet"}
        assert not cm.score_records([ok])[0].is_empty

    def test_missing_or_unknown_evidence_empty(self):
        doc = parse_pmml(BN)
        cm = compile_pmml(doc)
        assert cm.score_records([{"sprinkler": None, "grass": "wet"}])[0].is_empty
        assert evaluate(doc, {"sprinkler": None, "grass": "wet"}).value is None
        assert evaluate(doc, {"sprinkler": "sideways", "grass": "wet"}).value is None

    def test_rejections(self):
        # hidden (non-active, non-target) node
        with pytest.raises(ModelLoadingException, match="fully-observed"):
            parse_pmml(BN.replace('<MiningField name="sprinkler"/>', ""))
        # unknown parent (renamed consistently in both sprinkler rows)
        sprinkler_block = BN[
            BN.index('<DiscreteNode name="sprinkler">'):
            BN.index('<DiscreteNode name="grass">')
        ]
        with pytest.raises(ModelLoadingException, match="unknown parent"):
            parse_pmml(BN.replace(
                sprinkler_block,
                sprinkler_block.replace('parent="rain"', 'parent="wind"'),
            ))
        # value lists must agree across rows
        with pytest.raises(ModelLoadingException, match="disagree"):
            parse_pmml(BN.replace(
                '<ValueProbability value="on" probability="0.4"/>',
                '<ValueProbability value="ON" probability="0.4"/>',
            ))

    def test_dp_sharded(self):
        from flink_jpmml_tpu.parallel import make_mesh
        from flink_jpmml_tpu.parallel.sharding import dp_sharded
        from flink_jpmml_tpu.utils.config import MeshConfig
        from flink_jpmml_tpu.compile import prepare

        import jax

        if len(jax.devices()) < 8:
            # JAX_PLATFORMS=tpu on a 1-chip host: the virtual
            # 8-CPU mesh is unavailable; the sharding path is covered by
            # the CPU-mesh run (tests/conftest.py)
            pytest.skip("needs the 8-device virtual mesh")

        doc = parse_pmml(BN)
        cm = compile_pmml(doc)
        rng = np.random.default_rng(0)
        recs = [
            {
                "sprinkler": str(rng.choice(["on", "off"])),
                "grass": str(rng.choice(["wet", "dry"])),
            }
            for _ in range(64)
        ]
        X, M = prepare.from_records(cm.field_space, recs)
        ref = cm.predict(X, M)
        sm = dp_sharded(cm, make_mesh(MeshConfig(data=8, model=1)))
        out = sm.predict(X, M)
        np.testing.assert_allclose(
            np.asarray(out.value), np.asarray(ref.value), rtol=1e-5
        )
        np.testing.assert_array_equal(
            np.asarray(out.label_idx), np.asarray(ref.label_idx)
        )
