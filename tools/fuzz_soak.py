"""Extended fuzz soak: arbitrary seed ranges over the test_fuzz
generators, on any backend — the on-device evidence tool behind the
"N seeds on-device clean" claims in docs/parity.md.

The pytest suite pins fixed seed ranges so CI stays deterministic and
fast; this driver reuses the exact same generators and the exact same
lane-by-lane compiled-vs-oracle assertion, but sweeps as many seeds as
a soak budget allows, on whichever backend the session resolves
(run plainly for the real chip, under ``JAX_PLATFORMS=cpu`` for the host).

Usage:
  python tools/fuzz_soak.py [--families trees,mining,regression,...]
                            [--seeds 100] [--start 10000]
Prints one summary line per family and exits nonzero on any parity
failure (the failing seed is in the assertion message — replay it by
passing --start <seed> --seeds 1).
"""

import argparse
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import numpy as np

from tests import test_fuzz as tf


def _soak_trees(seed):
    rng = np.random.default_rng(seed)
    doc, recs = None, None
    doc = tf._doc(tf._rand_tree_model(rng))
    recs = tf._rand_records(rng, 64)
    tf._assert_parity(doc, recs, f"tree seed={seed}")


def _soak_mining(seed):
    # mirrors TestFuzzMining.test_random_regression_ensemble_parity
    # (the generator is inline there, not a module helper)
    from flink_jpmml_tpu.pmml import ir

    rng = np.random.default_rng(seed)
    n_seg = int(rng.integers(2, 5))
    segments = tuple(
        ir.Segment(
            predicate=(
                ir.TruePredicate()
                if rng.random() < 0.5
                else tf._rand_predicate(rng, 1)
            ),
            model=ir.TreeModelIR(
                function_name="regression",
                mining_schema=tf._schema(),
                root=tf._rand_tree(rng, False, max_depth=2),
                missing_value_strategy=str(rng.choice(
                    ["none", "defaultChild", "nullPrediction"]
                )),
                split_characteristic="multiSplit",
            ),
            segment_id=f"s{i}",
            weight=float(np.round(rng.uniform(0.5, 2.0), 2)),
        )
        for i in range(n_seg)
    )
    method = str(rng.choice(
        ["sum", "average", "weightedAverage", "max", "median",
         "selectFirst"]
    ))
    model = ir.MiningModelIR(
        function_name="regression",
        mining_schema=tf._schema(),
        segmentation=ir.Segmentation(
            multiple_model_method=method, segments=segments
        ),
    )
    doc = tf._doc(model)
    recs = tf._rand_records(rng, 32)
    tf._assert_parity(doc, recs, f"mining {method} seed={seed}")


def _soak_regression(seed):
    rng = np.random.default_rng(seed)
    doc = tf._doc(tf._rand_regression_model(rng))
    recs = tf._rand_records(rng, 64)
    tf._assert_parity(doc, recs, f"regression seed={seed}")


def _soak_neural(seed):
    rng = np.random.default_rng(seed)
    doc = tf._doc(tf._rand_nn_model(rng))
    recs = tf._rand_records(rng, 64)
    tf._assert_parity(doc, recs, f"neural seed={seed}")


def _soak_glm(seed):
    rng = np.random.default_rng(seed)
    doc = tf._doc(tf._rand_glm_model(rng))
    recs = tf._rand_records(rng, 64)
    tf._assert_parity(doc, recs, f"glm seed={seed}")


def _soak_scorecard(seed):
    # mirrors TestFuzzScorecard.test_random_scorecard_parity
    from flink_jpmml_tpu.pmml import ir

    rng = np.random.default_rng(seed)
    chars = []
    for ci in range(int(rng.integers(1, 4))):
        attrs = [
            ir.ScorecardAttribute(
                predicate=tf._rand_predicate(rng, 1),
                partial_score=float(np.round(rng.normal(0, 20), 1)),
            )
            for _ in range(int(rng.integers(1, 4)))
        ]
        if rng.random() < 0.8:
            attrs.append(ir.ScorecardAttribute(
                predicate=ir.TruePredicate(),
                partial_score=float(np.round(rng.normal(0, 5), 1)),
            ))
        chars.append(ir.Characteristic(
            name=f"ch{ci}", attributes=tuple(attrs)
        ))
    model = ir.ScorecardIR(
        function_name="regression",
        mining_schema=tf._schema(),
        characteristics=tuple(chars),
        initial_score=float(np.round(rng.normal(100, 20), 1)),
        use_reason_codes=False,
    )
    doc = tf._doc(model)
    recs = tf._rand_records(rng, 40)
    tf._assert_parity(doc, recs, f"scorecard seed={seed}")


def _soak_sarima(seed):
    # mirrors TestFuzzArima.test_random_sarima_parity
    from flink_jpmml_tpu.pmml import parse_pmml
    from tests.test_timeseries import _arima_xml, _ns, _sc

    rng = np.random.default_rng(seed)
    p = int(rng.integers(0, 3))
    d = int(rng.integers(0, 2))
    q = int(rng.integers(0, 3))
    s = int(rng.integers(2, 5)) if rng.random() < 0.6 else 0
    P = int(rng.integers(0, 2)) if s else 0
    D = int(rng.integers(0, 2)) if s else 0
    Q = int(rng.integers(0, 2)) if s else 0
    if s and not (P or D or Q):
        D = 1

    def coefs(n):
        return tuple(round(float(v), 3)
                     for v in rng.uniform(-0.65, 0.65, size=n))

    n_res = q + s * Q
    residuals = tuple(
        round(float(v), 3) for v in rng.normal(0, 0.4, size=n_res)
    )
    n_hist = d + s * D + (p + s * P) + int(rng.integers(8, 16))
    t = np.arange(n_hist)
    hist = tuple(
        round(float(v), 3)
        for v in 40
        + 0.8 * t
        + (4 * np.sin(2 * np.pi * t / s) if s else 0)
        + rng.normal(0, 1.0, size=n_hist)
    )
    transformation = str(
        rng.choice(("none", "none", "logarithmic", "squareroot"))
    )
    body = _ns(p, d, q, ar=coefs(p), ma=coefs(q),
               residuals=residuals if n_res else ())
    if s:
        body += _sc(P, D, Q, s, sar=coefs(P), sma=coefs(Q))
    doc = parse_pmml(_arima_xml(
        body, hist,
        constant=round(float(rng.uniform(-0.5, 0.5)), 3),
        transformation=transformation,
    ))
    recs = []
    for _ in range(24):
        roll = rng.random()
        if roll < 0.1:
            recs.append({})
        elif roll < 0.2:
            recs.append({"h": None})
        elif roll < 0.3:
            recs.append({"h": float(rng.uniform(0.6, 20.0))})
        else:
            recs.append({"h": int(rng.integers(1, 31))})
    tf._assert_parity(doc, recs, f"sarima seed={seed}")


FAMILIES = {
    "trees": _soak_trees,
    "mining": _soak_mining,
    "regression": _soak_regression,
    "neural": _soak_neural,
    "glm": _soak_glm,
    "scorecard": _soak_scorecard,
    "sarima": _soak_sarima,
}


# --chaos mode: one compiled model shared across every seed (the chaos
# is in the FAULT composition, not the model)
_CHAOS_MODEL = None


def _chaos_model():
    global _CHAOS_MODEL
    if _CHAOS_MODEL is None:
        import tempfile

        from flink_jpmml_tpu.assets_gen import gen_gbm
        from flink_jpmml_tpu.compile import compile_pmml
        from flink_jpmml_tpu.pmml import parse_pmml_file

        tmp = tempfile.mkdtemp(prefix="fjt-chaos-model-")
        _CHAOS_MODEL = compile_pmml(
            parse_pmml_file(
                gen_gbm(tmp, n_trees=4, depth=3, n_features=5)
            ),
            batch_size=32,
        )
    return _CHAOS_MODEL


def _soak_chaos(seed):
    """One chaos iteration: a seeded random COMPOSITION of fault kinds
    (broker death, slow fetch, dispatch delay, checkpoint failure,
    worker wedge, poison records, decode poison, DEVICE faults —
    everything except worker_crash and chip_loss, which would kill the
    soak process itself; the kill-anywhere half lives in ``bench.py
    --recovery-drill`` / ``--device-fault-drill``) against a real
    Kafka→BlockPipeline stream with checkpoints + DLQ. Verifies the
    delivery contract every time: every offset either reaches the sink
    or sits in the DLQ, poison lands in the DLQ exactly — and device
    faults land NOWHERE (the ladder re-dispatches or serves the
    fallback tier; a sick device must never quarantine clean records
    nor lose any, even composed with e.g. a concurrent broker death) —
    and the stream drains to the end despite the weather."""
    import os
    import tempfile

    from flink_jpmml_tpu.runtime import faults
    from flink_jpmml_tpu.runtime.block import BlockPipeline
    from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
    from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue
    from flink_jpmml_tpu.runtime.kafka import (
        KafkaBlockSource, MiniKafkaBroker,
    )
    from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    rng = np.random.default_rng(seed)
    cm = _chaos_model()
    N = 1500
    data = rng.normal(0, 1.0, size=(N, 5)).astype(np.float32)
    tmp = tempfile.mkdtemp(prefix="fjt-chaos-")
    broker = MiniKafkaBroker(topic="chaos")
    pipe = None
    try:
        # interleave decode poison at random positions
        decode_offsets = []
        positions = sorted(
            int(p) for p in rng.choice(
                N, size=int(rng.integers(0, 3)), replace=False,
            )
        )
        produced = 0
        for p in positions:
            broker.append_rows(data[produced:p])
            decode_offsets.append(broker.append(b"chaff"))
            produced = p
        broker.append_rows(data[produced:])
        total = N + len(decode_offsets)
        # score poison via the harness, offsets in the log domain
        score_poison = []
        for _ in range(int(rng.integers(0, 3))):
            o = int(rng.integers(0, total))
            while o in decode_offsets or o in score_poison:
                o = (o + 1) % total
            score_poison.append(o)
        spec = [
            f"poison_record:offset={o}" for o in score_poison
        ]
        menu = [
            f"slow_fetch:delay_ms=2:p=0.05:seed={seed}",
            f"broker_death:n={int(rng.integers(1, 3))}"
            f":p=0.02:seed={seed}",
            f"dispatch_delay:delay_ms=1:p=0.05:seed={seed}",
            f"checkpoint_fail:n={int(rng.integers(1, 3))}",
            "worker_wedge:wedge_s=0.05:n=1",
            # device kinds (runtime/devfault.py): persistent-ish error
            # streaks exercise redispatch→breaker→fallback, OOM streaks
            # the batch-size bisection — composed freely with the rest
            f"device_error:site=device_readback"
            f":n={int(rng.integers(2, 10))}",
            f"device_oom:site=device_dispatch"
            f":n={int(rng.integers(1, 4))}",
        ]
        picks = rng.choice(
            len(menu), size=int(rng.integers(1, len(menu) + 1)),
            replace=False,
        )
        spec += [menu[i] for i in picks]
        emitted = []

        def sink(out, n, first_off):
            emitted.append((first_off, n))

        m = MetricsRegistry()
        dlq = DeadLetterQueue(os.path.join(tmp, "ck", "dlq"), metrics=m)
        src = KafkaBlockSource(
            broker.host, broker.port, "chaos", n_cols=5,
            max_wait_ms=10, metrics=m, dlq=dlq,
        )
        os.environ["FJT_RETRY_BASE_S"] = "0.01"
        # fast breaker geometry so a device_error streak can complete
        # its open→half-open→closed lifecycle within one soak seed
        os.environ["FJT_FAILOVER_COOLDOWN_S"] = "0.1"
        os.environ["FJT_FAILOVER_GREENS"] = "1"
        assert faults.install_from_env(",".join(spec)), spec
        pipe = BlockPipeline(
            src, cm, sink,
            RuntimeConfig(
                batch=BatchConfig(size=32, deadline_us=1000),
                checkpoint_interval_s=0.05,
            ),
            metrics=m,
            checkpoint=CheckpointManager(os.path.join(tmp, "ck")),
            dlq=dlq,
            max_dispatch_chunks=4,
        )
        pipe.start()
        deadline = time.perf_counter() + 60.0
        while (
            pipe.committed_offset < total
            and pipe._error is None
            and time.perf_counter() < deadline
        ):
            time.sleep(0.01)
        pipe.stop()
        pipe.join(timeout=20.0)
        pipe = None
        src.close()
        assert pipe is None
        covered = np.zeros(total, np.int64)
        for off, n in emitted:
            covered[off: off + n] += 1
        quarantined = sorted(set(dlq.offsets()))
        expected = sorted(set(decode_offsets) | set(score_poison))
        assert quarantined == expected, (
            f"chaos seed={seed}: DLQ {quarantined} != {expected} "
            f"(spec {spec})"
        )
        missing = sorted(
            int(o) for o in np.flatnonzero(covered == 0)
        )
        assert missing == expected, (
            f"chaos seed={seed}: sink gaps {missing[:10]} != "
            f"quarantined {expected} (spec {spec})"
        )
    finally:
        faults.clear()
        if pipe is not None:
            try:
                pipe.stop()
                pipe.join(timeout=10.0)
            except Exception:
                pass
        broker.close()
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


def _soak_mesh_chaos(seed):
    """One MESH chaos iteration (PR 16): ``chip_loss`` — survivable on
    a mesh since the KIND_LOST rung rebuilds over the surviving chips
    in place — composed with kafka-side weather (slow fetch, broker
    death, dispatch delay) against a mesh-sharded Kafka→BlockPipeline
    stream. Verifies degraded-mesh mode under churn: every offset
    reaches the sink exactly (zero loss, zero duplication — no
    restart), the DLQ stays EMPTY (a dead chip never quarantines
    records), every injected chip loss performed a rebuild, and the
    surviving data width dropped accordingly."""
    import os
    import tempfile

    from flink_jpmml_tpu.parallel.mesh import make_mesh
    from flink_jpmml_tpu.runtime import faults
    from flink_jpmml_tpu.runtime.block import BlockPipeline
    from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
    from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue
    from flink_jpmml_tpu.runtime.kafka import (
        KafkaBlockSource, MiniKafkaBroker,
    )
    from flink_jpmml_tpu.utils.config import (
        BatchConfig, MeshConfig, RuntimeConfig,
    )
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    import jax

    n_dev = jax.device_count()
    assert n_dev >= 4, (
        f"mesh chaos needs >= 4 devices, found {n_dev} (set XLA_FLAGS="
        "--xla_force_host_platform_device_count=8)"
    )
    mesh = make_mesh(
        MeshConfig(data=4, model=2 if n_dev >= 8 else 1),
        allow_subset=True,
    )
    rng = np.random.default_rng(seed)
    cm = _chaos_model()
    N = 1504  # divides by 32; the mesh pad keeps partials dispatchable
    data = rng.normal(0, 1.0, size=(N, 5)).astype(np.float32)
    tmp = tempfile.mkdtemp(prefix="fjt-meshchaos-")
    broker = MiniKafkaBroker(topic="meshchaos")
    pipe = None
    try:
        broker.append_rows(data)
        # chip loss is the profile's anchor; width 4 survives two
        losses = int(rng.integers(1, 3))
        spec = [f"chip_loss:n={losses}"]
        menu = [
            f"slow_fetch:delay_ms=2:p=0.05:seed={seed}",
            f"broker_death:n={int(rng.integers(1, 3))}"
            f":p=0.02:seed={seed}",
            f"dispatch_delay:delay_ms=1:p=0.05:seed={seed}",
        ]
        picks = rng.choice(
            len(menu), size=int(rng.integers(1, len(menu) + 1)),
            replace=False,
        )
        spec += [menu[i] for i in picks]
        emitted = []

        def sink(out, n, first_off):
            emitted.append((first_off, n))

        m = MetricsRegistry()
        dlq = DeadLetterQueue(os.path.join(tmp, "ck", "dlq"), metrics=m)
        src = KafkaBlockSource(
            broker.host, broker.port, "meshchaos", n_cols=5,
            max_wait_ms=10, metrics=m, dlq=dlq,
        )
        os.environ["FJT_RETRY_BASE_S"] = "0.01"
        assert faults.install_from_env(",".join(spec)), spec
        pipe = BlockPipeline(
            src, cm, sink,
            RuntimeConfig(
                batch=BatchConfig(size=32, deadline_us=1000),
                checkpoint_interval_s=0.05,
            ),
            metrics=m,
            checkpoint=CheckpointManager(os.path.join(tmp, "ck")),
            dlq=dlq,
            max_dispatch_chunks=4,
            mesh=mesh,
        )
        pipe.start()
        deadline = time.perf_counter() + 120.0
        while (
            pipe.committed_offset < N
            and pipe._error is None
            and time.perf_counter() < deadline
        ):
            time.sleep(0.01)
        pipe.stop()
        pipe.join(timeout=20.0)
        err = pipe._error
        pipe = None
        src.close()
        assert err is None, f"mesh chaos seed={seed}: died {err!r}"
        covered = np.zeros(N, np.int64)
        for off, n in emitted:
            covered[off: off + n] += 1
        assert (covered == 1).all(), (
            f"mesh chaos seed={seed}: coverage "
            f"min={covered.min()} max={covered.max()} (spec {spec})"
        )
        assert sorted(set(dlq.offsets())) == [], (
            f"mesh chaos seed={seed}: chip loss quarantined records"
        )
        fired = faults.stats().get("chip_loss", 0)
        c = m.struct_snapshot()["counters"]
        assert c.get("mesh_rebuilds", 0) >= fired >= 1, (
            f"mesh chaos seed={seed}: {fired} chip losses but "
            f"{c.get('mesh_rebuilds', 0)} rebuilds (spec {spec})"
        )
    finally:
        faults.clear()
        if pipe is not None:
            try:
                pipe.stop()
                pipe.join(timeout=10.0)
            except Exception:
                pass
        broker.close()
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


_ZOO_DOCS = []


def _zoo_docs():
    """Six tiny distinct GBMs, built once per soak process (the chaos
    seeds churn tenants, not documents)."""
    if not _ZOO_DOCS:
        import tempfile

        from flink_jpmml_tpu.assets_gen import gen_gbm

        tmp = tempfile.mkdtemp(prefix="fjt-zoochaos-docs-")
        _ZOO_DOCS.extend(
            gen_gbm(tmp, n_trees=4 + i, depth=3, n_features=4,
                    seed=70 + i, name=f"zc{i}")
            for i in range(6)
        )
    return _ZOO_DOCS


def _soak_zoo_chaos(seed):
    """One ZOO chaos iteration: seeded tenant churn (Del / re-Add /
    version bump) composed with device faults against a zoo-enabled
    DynamicScorer. Verifies the per-tenant delivery contract every
    round: every submitted record gets exactly one prediction (C5
    totality), warm-served tenants' lanes are non-empty — a device
    fault mid-pack must redispatch, never surface — and unserved
    (churned-out) tenants' lanes are empty, never misrouted to a
    packmate."""
    import os
    import time as _t

    from flink_jpmml_tpu.models.control import AddMessage, DelMessage
    from flink_jpmml_tpu.models.core import ModelId
    from flink_jpmml_tpu.runtime import faults
    from flink_jpmml_tpu.runtime.sources import ControlSource
    from flink_jpmml_tpu.serving.scorer import DynamicScorer

    rng = np.random.default_rng(seed)
    docs = _zoo_docs()
    tenants = [f"zc{i}" for i in range(len(docs))]
    fields = [f"f{j}" for j in range(4)]
    data = rng.normal(0, 1.2, size=(4096, 4)).astype(np.float32)
    data[rng.random(size=data.shape) < 0.02] = np.nan

    os.environ["FJT_RETRY_BASE_S"] = "0.01"
    ctrl = ControlSource()
    sc = DynamicScorer(control=ctrl, batch_size=128, auto_rollout=False,
                       zoo=True)
    version = {}
    served = {}  # name -> every version currently registered: a Del
    # must cover ALL of them — deleting only the newest correctly
    # falls back to the older served version (latest-wins), which is
    # not "dead"
    for i, name in enumerate(tenants):
        version[name] = 1
        served[name] = {1}
        ctrl.push(AddMessage(name, 1, docs[i], timestamp=_t.time()))
    sc._drain_control()
    live = set(tenants)

    def wait_live(timeout_s=120.0):
        deadline = _t.monotonic() + timeout_s
        for name in sorted(live):
            mid = ModelId(name, version[name])
            while sc.registry.model_if_warm(mid) is None:
                err = sc.registry.warm_error(mid)
                assert err is None, (
                    f"zoo chaos seed={seed}: {mid.key()} warm "
                    f"failed {err!r}"
                )
                assert _t.monotonic() < deadline, (
                    f"zoo chaos seed={seed}: {mid.key()} never warmed"
                )
                _t.sleep(0.005)

    wait_live()
    cursor = 0
    try:
        for rnd in range(8):
            # seeded churn between rounds: Del a live tenant, revive a
            # dead one, or bump a live tenant's version (same document
            # - the swap re-packs, the outputs stay total)
            act = rng.integers(0, 4)
            if act == 0 and len(live) > 2:
                victim = sorted(live)[int(rng.integers(0, len(live)))]
                # a version bump leaves the PRIOR version served;
                # latest-wins routing falls back to it after a Del of
                # the newest — "dead" means NO version remains, so the
                # Del must cover every version ever registered
                for v in sorted(served[victim]):
                    ctrl.push(DelMessage(victim, v,
                                         timestamp=_t.time()))
                served[victim] = set()
                live.discard(victim)
            elif act == 1 and len(live) < len(tenants):
                dead = sorted(set(tenants) - live)
                name = dead[int(rng.integers(0, len(dead)))]
                version[name] += 1
                served[name].add(version[name])
                ctrl.push(AddMessage(
                    name, version[name], docs[tenants.index(name)],
                    timestamp=_t.time(),
                ))
                live.add(name)
            elif act == 2:
                name = sorted(live)[int(rng.integers(0, len(live)))]
                version[name] += 1
                served[name].add(version[name])
                ctrl.push(AddMessage(
                    name, version[name], docs[tenants.index(name)],
                    timestamp=_t.time(),
                ))
            sc._drain_control()
            wait_live()
            if rng.random() < 0.6:
                # readback site only: the record-path scorer's fault
                # ladder lives in finish() (classify → redispatch); a
                # launch-time fault propagates to the BLOCK pipelines'
                # direct-dispatch handler, which this soak doesn't drive
                # streaks stay within the FJT_DEVICE_RETRIES budget
                # (2): the record path has no fallback tier below the
                # retry ladder — a longer streak escalates BY CONTRACT
                kind = ("device_error", "device_oom")[
                    int(rng.integers(0, 2))
                ]
                faults.inject(kind, site="device_readback",
                              n=int(rng.integers(1, 3)))
            rows = int(rng.integers(8, 64))
            ev, owner = [], []
            for name in tenants:
                for _ in range(rows):
                    rec = dict(zip(
                        fields, data[cursor % len(data)].tolist()
                    ))
                    rec["_key"] = f"k{cursor}"
                    cursor += 1
                    ev.append((name, rec))
                    owner.append(name)
            out = sc.finish(sc.submit(ev))
            assert len(out) == len(ev), (
                f"zoo chaos seed={seed} round={rnd}: "
                f"{len(out)} predictions for {len(ev)} records"
            )
            for (p, _), name in zip(out, owner):
                if name in live:
                    assert not p.is_empty, (
                        f"zoo chaos seed={seed} round={rnd}: live "
                        f"tenant {name} got an empty lane"
                    )
                else:
                    assert p.is_empty, (
                        f"zoo chaos seed={seed} round={rnd}: dead "
                        f"tenant {name} got a prediction (misrouted "
                        "packmate output)"
                    )
    finally:
        faults.clear()


# --chaos --stateful: the keyed-state profile. The worker must be a
# SUBPROCESS (unlike _soak_chaos) because the profile's crash axis is
# real SIGKILLs — parent kills at seeded committed-offset targets plus
# in-worker ``worker_crash`` weather — and the parity claim is about
# what survives them. One tiny GBM per soak process, like _chaos_model.
_STATE_PMML = []


def _state_chaos_pmml():
    if not _STATE_PMML:
        import tempfile

        from flink_jpmml_tpu.assets_gen import gen_gbm

        tmp = tempfile.mkdtemp(prefix="fjt-statechaos-model-")
        _STATE_PMML.append(
            gen_gbm(tmp, n_trees=4, depth=3, n_features=5)
        )
    return _STATE_PMML[0]


_STATE_CHAOS_WORKER = r'''
import os, sys, time
# per-incarnation fault seed BEFORE the package imports (env faults arm
# at import): seeded p-gates draw a fresh pattern per incarnation, so a
# site-targeted crash can't deterministically re-fire forever
os.environ["FJT_FAULTS"] = os.environ.get("FJT_FAULTS", "").replace(
    "PIDSEED", str(os.getpid())
)
sys.path.insert(0, sys.argv[10])
import jax
jax.config.update("jax_platforms", "cpu")  # correctness soak: host-side
import numpy as np
from flink_jpmml_tpu.compile import compile_pmml
from flink_jpmml_tpu.pmml import parse_pmml_file
from flink_jpmml_tpu.runtime.block import BlockPipeline, FiniteBlockSource
from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue
from flink_jpmml_tpu.runtime import state as state_mod
from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig
from flink_jpmml_tpu.utils.metrics import MetricsRegistry

pmml, ckdir, outpath, emitpath = sys.argv[1:5]
seed, records, keys, capacity, B = (int(v) for v in sys.argv[5:10])
# every incarnation regenerates the IDENTICAL keyed stream from the
# seed — the chaos is in the faults, the stream is the constant
rng = np.random.default_rng(seed)
data = rng.normal(0.0, 1.0, size=(records, 5)).astype(np.float32)
data[:, 0] = rng.integers(0, keys, size=records).astype(np.float32)
cm = compile_pmml(parse_pmml_file(pmml), batch_size=B)
m = MetricsRegistry()
dlq = DeadLetterQueue(os.path.join(ckdir, "dlq"), metrics=m)
emit = open(emitpath, "a", buffering=1)

def sink(out, n, first_off):
    emit.write("%d %d\n" % (first_off, n))

pipe = BlockPipeline(
    # block == dispatch batch + a far fill deadline: every dispatch is
    # one aligned B-sized block, so a restore replays the exact batch
    # boundaries of the reference life (the byte-parity precondition —
    # scatter-add order is fixed within a batch, reassociated across a
    # different split)
    FiniteBlockSource(data, block_size=B), cm, sink,
    RuntimeConfig(
        batch=BatchConfig(size=B, deadline_us=5_000_000),
        checkpoint_interval_s=0.05,
    ),
    metrics=m,
    checkpoint=CheckpointManager(ckdir),
    dlq=dlq,
    state=state_mod.StateSpec(capacity=capacity, key_col=0),
)
pipe.restore()
pipe.start()
while pipe.committed_offset < records and pipe._error is None:
    time.sleep(0.02)
pipe.stop()
pipe.join(timeout=30.0)
if pipe._error is not None:
    raise SystemExit("state chaos worker died: %r" % (pipe._error,))
tbl = pipe._state
jax.block_until_ready(tbl.values)
c = m.struct_snapshot()["counters"]
tmp_out = outpath + ".tmp"
np.savez(
    tmp_out,
    values=np.asarray(tbl.values),
    keys=tbl._keys, occ=tbl._occ,
    applied_hi=np.int64(tbl.applied_hi),
    state_rollbacks=np.int64(c.get("state_rollbacks", 0)),
)
os.replace(tmp_out + ".npz", outpath)  # np.savez appends .npz
emit.close()
'''


def _soak_stateful_chaos(seed):
    """One STATEFUL chaos iteration (ISSUE 19): seeded faults —
    worker crashes (parent SIGKILLs at committed-offset targets plus
    in-worker ``worker_crash`` weather), ``device_oom``/``device_error``
    streaks, and ``poison_record`` offsets — against a keyed stream
    through a state-armed checkpointed BlockPipeline, run as supervised
    subprocess incarnations. Per seed, against a same-poison fault-free
    reference life:

    - delivery contract (every life): the stream drains, the DLQ holds
      the poison offsets EXACTLY, and the sink's only gaps are those
      quarantined offsets — crashes and device faults lose nothing and
      quarantine nothing;
    - exactly-once fold accounting (every life): NO key ever folds
      MORE records than its ground-truth occurrence count in the
      seeded stream — no crash/replay/re-dispatch composition may
      double-fold. Folding FEWER is legitimate only for rollback
      seeds: a dispatch error (poison or device fault) restores the
      last checkpoint snapshot, shedding a wall-clock-sized window of
      folds by design (bounded, counted loss — ``state_rollbacks``);
    - state parity: when the composition has no rollback source (kills
      and ``worker_crash`` weather only), every key's fold count must
      equal ground truth exactly AND the final table must be
      BYTE-identical to an uninterrupted fault-free reference life —
      the bench kill-parity claim extended to crash weather with the
      DLQ wired."""
    import os
    import shutil
    import signal
    import subprocess
    import tempfile

    from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
    from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue

    rng = np.random.default_rng(seed)
    records, keys, capacity, B = 2048, 256, 2048, 32
    pmml = _state_chaos_pmml()
    repo = str(pathlib.Path(__file__).resolve().parent.parent)
    tmp = tempfile.mkdtemp(prefix="fjt-statechaos-")
    try:
        # ---- seeded composition --------------------------------------
        poison = []
        for _ in range(int(rng.integers(0, 3))):
            o = int(rng.integers(0, records))
            while o in poison:
                o = (o + 1) % records
            poison.append(o)
        pspec = [f"poison_record:offset={o}" for o in poison]
        kills = int(rng.integers(0, 3))
        if not poison and not kills:
            kills = 1  # never a degenerate fault-free seed
        weather, dev_budget = [], 0
        if rng.random() < 0.4:
            # SIGKILL-anywhere weather: parity-SAFE — exactly-once
            # restore covers any kill instant, in-worker or parent
            weather.append(
                "worker_crash:site=checkpoint_write:p=0.01:n=1"
                ":after_s=0.3:seed=PIDSEED"
            )
        if rng.random() < 0.5:
            dmenu = []
            for kind, site, lo, hi in (
                ("device_error", "device_readback", 2, 6),
                ("device_oom", "device_dispatch", 1, 4),
            ):
                n = int(rng.integers(lo, hi))
                dmenu.append((f"{kind}:site={site}:n={n}", n))
            picks = rng.choice(
                len(dmenu), size=int(rng.integers(1, len(dmenu) + 1)),
                replace=False,
            )
            weather += [dmenu[i][0] for i in picks]
            dev_budget = sum(dmenu[i][1] for i in picks)
        chaos_spec = pspec + weather
        if kills:
            # stretch the drain so the parent's committed-offset poll
            # can land its kills (pure delay: no state effect)
            chaos_spec.append("dispatch_delay:delay_ms=2")

        # ---- one supervised life -------------------------------------
        def run_life(tag, spec, kill_targets, timeout_s=150.0):
            ckdir = os.path.join(tmp, f"ck-{tag}")
            outpath = os.path.join(tmp, f"state-{tag}.npz")
            emitpath = os.path.join(tmp, f"emit-{tag}.log")
            open(emitpath, "w").close()
            argv = [
                sys.executable, "-c", _STATE_CHAOS_WORKER,
                pmml, ckdir, outpath, emitpath, str(seed),
                str(records), str(keys), str(capacity), str(B), repo,
            ]
            env = dict(os.environ)
            env.update({
                "JAX_PLATFORMS": "cpu",
                "FJT_FAULTS": ",".join(spec),
                "FJT_RETRY_BASE_S": "0.01",
                "FJT_FAILOVER_COOLDOWN_S": "0.1",
                "FJT_FAILOVER_GREENS": "1",
                "FJT_AUTOTUNE_CACHE": os.path.join(tmp, "autotune"),
            })

            def committed():
                try:
                    st = CheckpointManager(ckdir).load_latest()
                    return int(st["source_offset"]) if st else 0
                except Exception:
                    return 0

            pending = list(kill_targets)
            incarnations = 0
            deadline = time.monotonic() + timeout_s
            while True:
                assert incarnations < 25, (
                    f"stateful chaos seed={seed} ({tag}): restart "
                    f"storm without drain (spec {spec})"
                )
                assert time.monotonic() < deadline, (
                    f"stateful chaos seed={seed} ({tag}): no drain in "
                    f"{timeout_s}s, committed "
                    f"{committed()}/{records} (spec {spec})"
                )
                proc = subprocess.Popen(
                    argv, env=env, stdout=subprocess.DEVNULL,
                    stderr=subprocess.PIPE, text=True,
                )
                incarnations += 1
                killed_this = False
                while proc.poll() is None:
                    if pending and committed() >= pending[0]:
                        os.kill(proc.pid, signal.SIGKILL)
                        proc.wait(timeout=10)
                        pending.pop(0)
                        killed_this = True
                        break
                    if time.monotonic() >= deadline:
                        proc.kill()
                        proc.wait(timeout=10)
                        break
                    time.sleep(0.02)
                if killed_this:
                    continue
                if proc.returncode == 0:
                    assert os.path.exists(outpath), (
                        f"stateful chaos seed={seed} ({tag}): worker "
                        "exited 0 without its table dump"
                    )
                    return outpath, emitpath, ckdir, incarnations
                if proc.returncode == -signal.SIGKILL:
                    continue  # in-worker worker_crash weather: respawn
                raise AssertionError(
                    f"stateful chaos seed={seed} ({tag}): worker "
                    f"rc={proc.returncode} (spec {spec}): "
                    f"{(proc.stderr.read() or '')[-600:]}"
                )

        # a dispatch error rolls the table back to the LAST CHECKPOINT
        # snapshot (wall-clock interval ⇒ nondeterministic shed
        # window), so exact parity is only claimable for compositions
        # with no rollback source at all
        rollback_free = not poison and dev_budget == 0

        targets = [
            int(records * (i + 1) / (kills + 1)) for i in range(kills)
        ]
        ch_path, ch_emit, ch_ck, incarnations = run_life(
            "chaos", chaos_spec, targets,
        )
        lives = [("chaos", ch_path, ch_emit, ch_ck)]
        if rollback_free:
            ref_path, ref_emit, ref_ck, _ = run_life("ref", [], [])
            lives.append(("ref", ref_path, ref_emit, ref_ck))

        # ---- ground truth: the seeded stream's per-key-hash counts ---
        from flink_jpmml_tpu.parallel.partitioner import stable_hash_vec

        gt = np.random.default_rng(seed)
        gt.normal(0.0, 1.0, size=(records, 5))  # same draw order
        raw = gt.integers(0, keys, size=records).astype(np.float32)
        kh = stable_hash_vec(raw.astype(np.int64))
        uk, true_n = np.unique(kh, return_counts=True)
        true = dict(zip(uk.tolist(), true_n.tolist()))

        def counts(d):
            occ = d["occ"].astype(bool)
            # values carries scratch/padding rows past capacity; the
            # mirror indexes only the table proper
            vals = d["values"][: occ.shape[0]]
            return dict(zip(
                d["keys"][occ].tolist(), vals[occ, 0].tolist(),
            ))

        expected = sorted(poison)
        for tag, outpath, emitpath, ckdir in lives:
            # ---- delivery contract -----------------------------------
            covered = np.zeros(records, np.int64)
            with open(emitpath) as f:
                for line in f:
                    parts = line.split()
                    if len(parts) != 2:
                        continue  # torn final line at a SIGKILL
                    off, n = int(parts[0]), int(parts[1])
                    covered[off: off + n] += 1
            q = sorted(set(DeadLetterQueue(
                os.path.join(ckdir, "dlq")
            ).offsets()))
            assert q == expected, (
                f"stateful chaos seed={seed} ({tag}): DLQ {q} != "
                f"{expected} (spec {chaos_spec})"
            )
            missing = sorted(
                int(o) for o in np.flatnonzero(covered == 0)
            )
            assert missing == expected, (
                f"stateful chaos seed={seed} ({tag}): sink gaps "
                f"{missing[:10]} != quarantined {expected} "
                f"(spec {chaos_spec})"
            )
            # ---- exactly-once fold accounting ------------------------
            folded = counts(np.load(outpath))
            for k, n in folded.items():
                assert k in true and n <= true[k], (
                    f"stateful chaos seed={seed} ({tag}): key {k} "
                    f"folded {n} records vs {true.get(k, 0)} in the "
                    f"stream — a replay or re-dispatch double-folded "
                    f"(spec {chaos_spec})"
                )
            if rollback_free:
                deficit = sum(true.values()) - sum(folded.values())
                assert deficit == 0, (
                    f"stateful chaos seed={seed} ({tag}): {deficit} "
                    f"folds lost with no rollback source composed "
                    f"(spec {chaos_spec})"
                )

        # ---- byte parity (rollback-free compositions only) -----------
        if rollback_free:
            ref_v = np.load(ref_path)["values"]
            ch_v = np.load(ch_path)["values"]
            assert ref_v.tobytes() == ch_v.tobytes(), (
                f"stateful chaos seed={seed}: table diverged from the "
                f"fault-free reference after {incarnations} "
                f"incarnations / {kills} kills (spec {chaos_spec})"
            )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--families", default=",".join(FAMILIES))
    ap.add_argument("--seeds", type=int, default=50)
    ap.add_argument("--start", type=int, default=100_000)
    ap.add_argument("--chaos", action="store_true",
                    help="fault-composition soak instead of parity "
                         "families: each seed drives a random mix of "
                         "FJT_FAULTS kinds through a Kafka→pipeline "
                         "stream and verifies the delivery contract "
                         "(no loss, poison exactly in the DLQ)")
    ap.add_argument("--mesh", action="store_true",
                    help="with --chaos: the MESH profile instead — "
                         "chip_loss composed with kafka faults against "
                         "a mesh-sharded pipeline (simulated 8-device "
                         "host), verifying degraded-mesh serving under "
                         "churn")
    ap.add_argument("--zoo", action="store_true",
                    help="with --chaos: the ZOO profile instead — "
                         "tenant churn (Del/re-Add/version bump) "
                         "composed with device faults against the "
                         "packed multi-tenant scorer, verifying the "
                         "per-tenant delivery contract")
    ap.add_argument("--stateful", action="store_true",
                    help="with --chaos: the STATEFUL profile instead — "
                         "seeded worker crashes (SIGKILL), device_oom/"
                         "device_error streaks, and poison offsets "
                         "over a keyed stream through a state-armed "
                         "checkpointed pipeline (subprocess "
                         "incarnations), asserting state parity vs a "
                         "fault-free reference + the delivery "
                         "contract per seed")
    args = ap.parse_args()

    if args.mesh:
        # the virtual-device flag must land before the backend inits
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    import jax

    if args.mesh:
        jax.config.update("jax_platforms", "cpu")

    print(f"backend: {jax.default_backend()}", flush=True)
    failures = 0
    if args.chaos:
        if args.zoo:
            fn, name = _soak_zoo_chaos, "zoo-chaos"
        elif args.mesh:
            fn, name = _soak_mesh_chaos, "mesh-chaos"
        elif args.stateful:
            fn, name = _soak_stateful_chaos, "stateful-chaos"
        else:
            fn, name = _soak_chaos, "chaos"
        t0 = time.perf_counter()
        ok = 0
        for s in range(args.start, args.start + args.seeds):
            try:
                fn(s)
                ok += 1
            except AssertionError as e:
                failures += 1
                print(f"FAIL {name} seed={s}: {e}", flush=True)
        dt = time.perf_counter() - t0
        print(
            f"{name}: {ok}/{args.seeds} seeds clean in {dt:.1f}s",
            flush=True,
        )
        return 1 if failures else 0
    for fam in args.families.split(","):
        fam = fam.strip()
        if fam not in FAMILIES:
            print(f"unknown family {fam!r}; have {sorted(FAMILIES)}")
            return 2
        fn = FAMILIES[fam]
        t0 = time.perf_counter()
        ok = 0
        for s in range(args.start, args.start + args.seeds):
            try:
                fn(s)
                ok += 1
            except AssertionError as e:
                failures += 1
                print(f"FAIL {fam} seed={s}: {e}", flush=True)
        dt = time.perf_counter() - t0
        print(
            f"{fam}: {ok}/{args.seeds} seeds clean in {dt:.1f}s",
            flush=True,
        )
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
