"""Health plane + autoscale actuator + doctor (ISSUE 12).

The closed observability loop: declarative SLO rules over the merged
metric feed, hysteresis that a flapping metric cannot oscillate, the
actuator's cooldown / checkpoint-gate / bounds policy, the parent
supervisor's rescale protocol, and the doctor's evidence correlation —
plus the slow 2-process soak where a sustained induced breach drives
exactly one checkpoint -> rescale -> restore cycle with byte-identical
committed output.
"""

import json
import os
import socket
import subprocess
import sys
import time

import pytest

from flink_tensorflow_tpu.core.autoscale import (
    RESCALE_EXIT_CODE,
    AutoscaleActuator,
    AutoscaleConfig,
    AutoscaleDecision,
    AutoscaleSupervisor,
    read_decision,
    write_decision,
)
from flink_tensorflow_tpu.metrics.health import (
    BREACH,
    OK,
    WARN,
    HealthConfig,
    HealthEvaluator,
    SloRule,
    default_rules,
)
from flink_tensorflow_tpu.metrics.registry import MetricRegistry

# ---------------------------------------------------------------------------
# fixtures: deterministic snapshot sequences
# ---------------------------------------------------------------------------

EDGE_RULE = SloRule("edge-queue", "edge*_queue_depth", warn=4.0, breach=6.0,
                    sustain=2, clear_after=2, action="scale_up")


def snap(depth):
    return {"slow.0": {"edge0_src_queue_depth": float(depth)}}


def feed(evaluator, depths, t0=100.0, dt=1.0):
    fired = []
    for i, d in enumerate(depths):
        fired.extend(evaluator.evaluate_once(snap(d), now=t0 + i * dt))
    return fired


# ---------------------------------------------------------------------------
# SloRule selection + validation
# ---------------------------------------------------------------------------


class TestSloRule:
    def test_validation(self):
        with pytest.raises(ValueError, match="metric or expr"):
            SloRule("x", "", warn=1, breach=2).validate()
        with pytest.raises(ValueError, match="cmp"):
            SloRule("x", "m", warn=1, breach=2, cmp=">=").validate()
        with pytest.raises(ValueError, match="sustain"):
            SloRule("x", "m", warn=1, breach=2, sustain=0).validate()
        with pytest.raises(ValueError, match="breach threshold"):
            SloRule("x", "m", warn=2, breach=1).validate()
        with pytest.raises(ValueError, match="field"):
            SloRule("x", "m", warn=1, breach=2, field="p97").validate()
        SloRule("x", "m", warn=1, breach=2).validate()

    def test_subtasks_roll_up_to_worst(self):
        rule = SloRule("bp", "queue_depth", warn=4, breach=6)
        got = rule.observe({"op.0": {"queue_depth": 2.0},
                            "op.1": {"queue_depth": 9.0},
                            "checkpoint": {"queue_depth": 99.0}})
        # Job-level scopes stay out of the default "*" selector.
        assert got == {"op": 9.0}

    def test_metric_pattern_yields_per_edge_targets(self):
        got = EDGE_RULE.observe({
            "op.0": {"edge0_a_queue_depth": 3.0, "edge1_b_queue_depth": 7.0}})
        assert got == {"op/edge0_a_queue_depth": 3.0,
                       "op/edge1_b_queue_depth": 7.0}

    def test_scope_and_field_selection(self):
        rule = SloRule("ckpt", "duration_s", scope="checkpoint",
                       field="p95", warn=5, breach=30)
        got = rule.observe({"checkpoint": {"duration_s": {"p95": 12.0}},
                            "op.0": {"duration_s": {"p95": 50.0}}})
        assert got == {"checkpoint": 12.0}

    def test_expr_scalar_lands_on_job(self):
        rule = SloRule("free", "", warn=1, breach=2,
                       expr=lambda s: len(s))
        assert rule.observe({"a.0": {}, "b.0": {}}) == {"job": 2.0}

    def test_default_catalogue_validates_and_scales(self):
        rules = default_rules(channel_capacity=100)
        by_id = {r.id: r for r in rules}
        assert by_id["edge-queue"].warn == 50.0
        assert by_id["edge-queue"].breach == 90.0
        for r in rules:
            r.validate()


# ---------------------------------------------------------------------------
# hysteresis: sustained vs flapping
# ---------------------------------------------------------------------------


class TestHysteresis:
    def test_sustained_breach_escalates_after_sustain(self):
        ev = HealthEvaluator([EDGE_RULE])
        fired = feed(ev, [9, 9])
        assert [(t.old, t.new) for t in fired] == [(OK, BREACH)]
        assert ev.job_state() == BREACH

    def test_warn_band_escalates_to_warn_only(self):
        ev = HealthEvaluator([EDGE_RULE])
        fired = feed(ev, [5, 5, 5, 5])
        assert [(t.old, t.new) for t in fired] == [(OK, WARN)]

    def test_flapping_never_transitions(self):
        ev = HealthEvaluator([EDGE_RULE])
        fired = feed(ev, [9, 0] * 10)
        assert fired == []
        assert ev.job_state() == OK

    def test_flapping_cannot_deescalate_a_breach_either(self):
        ev = HealthEvaluator([EDGE_RULE])
        feed(ev, [9, 9])  # BREACH
        fired = feed(ev, [0, 9] * 10, t0=200.0)
        assert fired == []
        assert ev.job_state() == BREACH

    def test_deescalation_steps_one_level_per_clear_window(self):
        ev = HealthEvaluator([EDGE_RULE])
        feed(ev, [9, 9])
        fired = feed(ev, [0, 0, 0, 0], t0=200.0)
        assert [(t.old, t.new) for t in fired] == [(BREACH, WARN), (WARN, OK)]

    def test_rate_mode_differentiates_and_skips_first_sight(self):
        rule = SloRule("bp", "backpressure_s", warn=0.5, breach=0.85,
                       mode="rate", sustain=2, action="scale_up")
        ev = HealthEvaluator([rule])
        # Cumulative gauge: +0.9s of blocked time per 1s interval.
        fired = []
        for i, raw in enumerate([0.0, 0.9, 1.8, 2.7]):
            fired.extend(ev.evaluate_once(
                {"op.0": {"backpressure_s": raw}}, now=100.0 + i))
        # First sight yields no rate; breaches at ticks 2 and 3 sustain.
        assert [(t.old, t.new) for t in fired] == [(OK, BREACH)]
        assert fired[0].value == pytest.approx(0.9)

    def test_transitions_carry_rule_action(self):
        ev = HealthEvaluator([EDGE_RULE])
        (t,) = feed(ev, [9, 9])
        assert t.action == "scale_up"
        assert "edge-queue" in t.describe()


# ---------------------------------------------------------------------------
# credit-starvation SLO (flow-control plane)
# ---------------------------------------------------------------------------


class TestCreditStarvationRule:
    """The flow-control SLO: the starved clocks are CUMULATIVE
    seconds-parked-at-zero-credit gauges, so the rules run mode="rate" —
    the fraction of wall time the edge spent parked.  Two scope
    families carry them: RemoteSink edges publish
    ``edge.credit_starved_s`` under their operator scope, shuffle-plane
    writers publish ``credit_starved_s`` under
    ``shuffle.out.{task}.{n}.ch{k}``."""

    def _rule(self, rid):
        return next(r for r in default_rules() if r.id == rid)

    def test_catalogue_carries_both_scope_families(self):
        for rid in ("credit-starvation", "credit-starvation-shuffle"):
            rule = self._rule(rid)
            rule.validate()
            assert rule.mode == "rate"
            assert rule.action == "scale_up"

    def test_operator_scope_starved_clock_breaches_on_rate(self):
        # A RemoteSink edge parked 0.9s of every second: rate 0.9 >
        # breach 0.85, sustained 3 ticks (first sight yields no rate).
        ev = HealthEvaluator([self._rule("credit-starvation")])
        fired = []
        for i, raw in enumerate([0.0, 0.9, 1.8, 2.7]):
            fired.extend(ev.evaluate_once(
                {"rsink.0": {"edge.credit_starved_s": raw}}, now=100.0 + i))
        assert [(t.old, t.new) for t in fired] == [(OK, BREACH)]
        assert fired[0].action == "scale_up"
        assert fired[0].value == pytest.approx(0.9)

    def test_shuffle_scope_starved_clock_breaches_on_rate(self):
        ev = HealthEvaluator([self._rule("credit-starvation-shuffle")])
        fired = []
        for i, raw in enumerate([0.0, 0.9, 1.8, 2.7]):
            fired.extend(ev.evaluate_once(
                {"shuffle.out.op.0.ch0": {"credit_starved_s": raw}},
                now=100.0 + i))
        assert [(t.old, t.new) for t in fired] == [(OK, BREACH)]

    def test_briefly_parked_edge_stays_ok(self):
        # 10% of wall time at zero credit is normal coalescing weather —
        # well under warn (0.5), neither rule may fire.
        rules = [self._rule("credit-starvation"),
                 self._rule("credit-starvation-shuffle")]
        ev = HealthEvaluator(rules)
        fired = []
        for i, raw in enumerate([0.0, 0.1, 0.2, 0.3, 0.4]):
            fired.extend(ev.evaluate_once(
                {"rsink.0": {"edge.credit_starved_s": raw},
                 "shuffle.out.op.0.ch0": {"credit_starved_s": raw}},
                now=100.0 + i))
        assert fired == []


# ---------------------------------------------------------------------------
# paged KV economy SLOs (ISSUE 19): pool pressure + tier thrash
# ---------------------------------------------------------------------------


class TestKvEconomyRules:
    @staticmethod
    def _rule(rule_id):
        return {r.id: r for r in default_rules()}[rule_id]

    def test_catalogue_carries_both_kv_rules(self):
        by_id = {r.id: r for r in default_rules()}
        assert by_id["kv-pool-pressure"].action == "scale_up"
        assert by_id["kv-tier-thrash"].mode == "rate"

    def test_pool_pressure_breaches_on_sustained_occupancy(self):
        ev = HealthEvaluator([self._rule("kv-pool-pressure")])
        fired = []
        for i, pct in enumerate([96.0, 97.0]):
            fired.extend(ev.evaluate_once(
                {"serve.0": {"kv_page_occupancy_pct": pct}}, now=100.0 + i))
        assert [(t.old, t.new) for t in fired] == [(OK, BREACH)]
        assert fired[0].action == "scale_up"

    def test_pool_pressure_warn_band(self):
        ev = HealthEvaluator([self._rule("kv-pool-pressure")])
        fired = []
        for i in range(4):
            fired.extend(ev.evaluate_once(
                {"serve.0": {"kv_page_occupancy_pct": 88.0}}, now=100.0 + i))
        assert [(t.old, t.new) for t in fired] == [(OK, WARN)]

    def test_tier_thrash_rates_the_cumulative_move_counter(self):
        ev = HealthEvaluator([self._rule("kv-tier-thrash")])
        fired = []
        # 60 demote/revive transitions per second, sustained: thrash.
        for i, raw in enumerate([0.0, 60.0, 120.0, 180.0]):
            fired.extend(ev.evaluate_once(
                {"serve.0": {"kv_tier_moves": raw}}, now=100.0 + i))
        assert [(t.old, t.new) for t in fired] == [(OK, BREACH)]
        assert fired[0].value == pytest.approx(60.0)

    def test_slow_tier_movement_stays_ok(self):
        ev = HealthEvaluator([self._rule("kv-tier-thrash")])
        fired = []
        for i, raw in enumerate([0.0, 2.0, 4.0, 6.0]):
            fired.extend(ev.evaluate_once(
                {"serve.0": {"kv_tier_moves": raw}}, now=100.0 + i))
        assert fired == []

    def test_dense_plan_without_kv_metrics_never_fires(self):
        ev = HealthEvaluator([self._rule("kv-pool-pressure"),
                              self._rule("kv-tier-thrash")])
        fired = []
        for i in range(3):
            fired.extend(ev.evaluate_once(
                {"serve.0": {"active_seqs": 4.0}}, now=100.0 + i))
        assert fired == []


# ---------------------------------------------------------------------------
# evaluator publication: gauges, flight, rollups
# ---------------------------------------------------------------------------


class TestEvaluatorPublication:
    def test_health_gauges_land_in_registry(self):
        reg = MetricRegistry()
        ev = HealthEvaluator([EDGE_RULE], registry=reg)
        feed(ev, [9, 9])
        health = reg.snapshot()["health"]
        assert health["slow"] == BREACH
        assert health["job"] == BREACH

    def test_gauges_track_deescalation(self):
        reg = MetricRegistry()
        ev = HealthEvaluator([EDGE_RULE], registry=reg)
        feed(ev, [9, 9])
        feed(ev, [0, 0, 0, 0], t0=200.0)
        assert reg.snapshot()["health"]["slow"] == OK

    def test_per_edge_targets_fold_to_operator(self):
        ev = HealthEvaluator([EDGE_RULE])
        feed(ev, [9, 9])
        assert ev.target_states() == {"slow": BREACH}
        assert [(r.id, t) for r, t, _v in ev.active_breaches()] == \
            [("edge-queue", "slow/edge0_src_queue_depth")]

    def test_flight_records_every_transition(self):
        from flink_tensorflow_tpu.tracing.flight import FlightRecorder

        flight = FlightRecorder()
        ev = HealthEvaluator([EDGE_RULE], flight=flight)
        feed(ev, [9, 9])
        events = [e for e in flight.events() if e[0] == "health"]
        assert len(events) == 1
        assert events[0][5]["to"] == "BREACH"

    def test_health_view_shape(self):
        ev = HealthEvaluator([EDGE_RULE])
        feed(ev, [9, 9])
        view = ev.health()
        assert view["job"] == "BREACH"
        assert view["targets"] == {"slow": "BREACH"}
        assert view["transitions"]

    def test_config_validation(self):
        HealthConfig(rules=(EDGE_RULE,),
                     autoscale=AutoscaleConfig()).validate()
        with pytest.raises(ValueError, match="interval_s"):
            HealthConfig(interval_s=0.0).validate()
        with pytest.raises(ValueError, match="max_workers"):
            HealthConfig(autoscale=AutoscaleConfig(
                min_workers=3, max_workers=2)).validate()


# ---------------------------------------------------------------------------
# actuator policy
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def _actuator(tmp_path, clock, *, num_workers=2, max_workers=3,
              cooldown_s=5.0, checkpoint_ready=lambda: 7):
    cfg = AutoscaleConfig(
        min_workers=1, max_workers=max_workers, cooldown_s=cooldown_s,
        decision_path=str(tmp_path / "decision.json"))
    return AutoscaleActuator(cfg, num_workers, clock=clock,
                             checkpoint_ready=checkpoint_ready)


class TestActuator:
    def test_cooldown_defers_then_level_trigger_decides(self, tmp_path):
        clock = _Clock()
        act = _actuator(tmp_path, clock)
        ev = HealthEvaluator([EDGE_RULE])
        ev.subscribe_ticks(act.on_tick)
        feed(ev, [9, 9])
        # BREACH is active but the cooldown is running: deferred.
        assert act.last_verdict == "cooldown"
        assert act.decision is None
        clock.t = 6.0
        # No new transition edge — the next tick alone must decide.
        feed(ev, [9], t0=300.0)
        assert act.last_verdict == "decided"
        assert act.decision.action == "scale_up"
        assert act.decision.from_workers == 2
        assert act.decision.to_workers == 3
        assert act.decision.checkpoint_id == 7

    def test_checkpoint_gate_blocks_until_a_checkpoint_exists(self, tmp_path):
        clock = _Clock(10.0)
        cid = {"v": None}
        act = _actuator(tmp_path, clock, cooldown_s=0.0,
                        checkpoint_ready=lambda: cid["v"])
        ev = HealthEvaluator([EDGE_RULE])
        ev.subscribe_ticks(act.on_tick)
        feed(ev, [9, 9])
        assert act.last_verdict == "no-checkpoint"
        cid["v"] = 3
        feed(ev, [9], t0=300.0)
        assert act.decision is not None
        assert act.decision.checkpoint_id == 3

    def test_at_bounds_never_decides(self, tmp_path):
        clock = _Clock(10.0)
        act = _actuator(tmp_path, clock, num_workers=3, max_workers=3,
                        cooldown_s=0.0)
        ev = HealthEvaluator([EDGE_RULE])
        ev.subscribe_ticks(act.on_tick)
        feed(ev, [9, 9, 9, 9])
        assert act.decision is None
        assert act.last_verdict == "at-bounds"

    def test_one_decision_per_actuator_life(self, tmp_path):
        clock = _Clock(10.0)
        act = _actuator(tmp_path, clock, cooldown_s=0.0)
        ev = HealthEvaluator([EDGE_RULE])
        ev.subscribe_ticks(act.on_tick)
        feed(ev, [9] * 10)
        assert act.decision.to_workers == 3
        assert act.last_verdict == "decided"

    def test_flapping_fixture_never_actuates(self, tmp_path):
        clock = _Clock(10.0)
        act = _actuator(tmp_path, clock, cooldown_s=0.0)
        ev = HealthEvaluator([EDGE_RULE])
        ev.subscribe_ticks(act.on_tick)
        feed(ev, [9, 0] * 10)
        assert act.decision is None
        assert act.last_verdict == "no-breach"

    def test_scale_up_outranks_scale_down(self, tmp_path):
        idle = SloRule("idle", "idle_s", warn=4, breach=6, sustain=2,
                       clear_after=2, action="scale_down")
        clock = _Clock(10.0)
        act = _actuator(tmp_path, clock, cooldown_s=0.0)
        ev = HealthEvaluator([EDGE_RULE, idle])
        ev.subscribe_ticks(act.on_tick)
        for i in range(2):
            ev.evaluate_once({"slow.0": {"edge0_src_queue_depth": 9.0},
                              "lazy.0": {"idle_s": 9.0}}, now=100.0 + i)
        assert act.decision.action == "scale_up"
        assert act.decision.rule_id == "edge-queue"

    def test_decision_file_round_trip(self, tmp_path):
        clock = _Clock(10.0)
        act = _actuator(tmp_path, clock, cooldown_s=0.0)
        ev = HealthEvaluator([EDGE_RULE])
        ev.subscribe_ticks(act.on_tick)
        feed(ev, [9, 9])
        doc = read_decision(str(tmp_path / "decision.json"))
        assert doc is not None
        assert doc["to_workers"] == 3
        assert doc["rule_id"] == "edge-queue"
        assert doc["health"]["job"] == "BREACH"

    def test_read_decision_rejects_garbage(self, tmp_path):
        path = str(tmp_path / "d.json")
        assert read_decision(path) is None
        with open(path, "w") as f:
            f.write("{not json")
        assert read_decision(path) is None
        with open(path, "w") as f:
            json.dump({"kind": "something-else"}, f)
        assert read_decision(path) is None


# ---------------------------------------------------------------------------
# supervisor protocol (no record plane: trivial worker commands)
# ---------------------------------------------------------------------------


def _decision_writer_code(path, to_workers, exit_code=RESCALE_EXIT_CODE):
    decision = AutoscaleDecision(
        rule_id="edge-queue", target="slow", action="scale_up", value=9.0,
        from_workers=2, to_workers=to_workers, ts=0.0)
    doc = decision.to_dict()
    return (
        "import json, sys, time\n"
        f"doc = {doc!r}\n"
        "doc['ts'] = time.time()\n"
        f"json.dump(doc, open({path!r}, 'w'))\n"
        f"sys.exit({exit_code})\n"
    )


class TestAutoscaleSupervisor:
    def test_rescale_request_respawns_at_decision_target(self, tmp_path):
        path = str(tmp_path / "decision.json")

        def command(w, num_workers, attempt):
            if attempt == 0 and w == 0:
                return [sys.executable, "-S", "-c",
                        _decision_writer_code(path, 3)]
            if attempt == 0:
                # The deciding worker's peer: killed by the supervisor.
                return [sys.executable, "-S", "-c",
                        "import time; time.sleep(60)"]
            return [sys.executable, "-S", "-c",
                    f"import sys; sys.exit(0 if {num_workers} == 3 else 9)"]

        sup = AutoscaleSupervisor(command, 2, decision_path=path,
                                  max_workers=3, poll_s=0.02)
        outcome = sup.run()
        assert outcome.returncode == 0
        assert outcome.attempts == 2
        assert outcome.num_workers == 3
        assert len(outcome.rescales) == 1
        assert outcome.rescales[0]["to_workers"] == 3

    def test_decision_target_is_reclamped(self, tmp_path):
        path = str(tmp_path / "decision.json")

        def command(w, num_workers, attempt):
            if attempt == 0 and w == 0:
                # A decision demanding more than the parent allows.
                return [sys.executable, "-S", "-c",
                        _decision_writer_code(path, 99)]
            if attempt == 0:
                # The deciding worker's peer, killed by the supervisor: a
                # peer that exited first with its own code would race the
                # decision file, and the attempt would read as a failure.
                return [sys.executable, "-S", "-c",
                        "import time; time.sleep(60)"]
            return [sys.executable, "-S", "-c",
                    f"import sys; sys.exit(0 if {num_workers} == 3 else 9)"]

        sup = AutoscaleSupervisor(command, 2, decision_path=path,
                                  max_workers=3, poll_s=0.02)
        outcome = sup.run()
        assert outcome.num_workers == 3

    def test_rescale_exit_without_decision_burns_budget(self, tmp_path):
        path = str(tmp_path / "decision.json")  # never written
        attempts = []

        def command(w, num_workers, attempt):
            attempts.append((attempt, num_workers))
            rc = RESCALE_EXIT_CODE if attempt == 0 else 0
            return [sys.executable, "-S", "-c",
                    f"import sys; sys.exit({rc})"]

        sup = AutoscaleSupervisor(command, 2, decision_path=path,
                                  max_workers=3, max_restarts=2,
                                  poll_s=0.02)
        outcome = sup.run()
        # Respawned UNCHANGED: a lost decision file must not guess.
        assert outcome.num_workers == 2
        assert outcome.rescales == ()

    def test_stale_decision_is_not_reconsumed(self, tmp_path):
        path = str(tmp_path / "decision.json")
        write_decision(path, AutoscaleDecision(
            rule_id="old", target="x", action="scale_up", value=1.0,
            from_workers=2, to_workers=3, ts=time.time()))
        sup = AutoscaleSupervisor(lambda w, n, a: [], 2,
                                  decision_path=path, max_workers=3)
        # A decision consumed at ts must not match afterwards.
        doc = sup._fresh_decision(0.0)
        assert doc is not None
        assert sup._fresh_decision(float(doc["ts"])) is None

    def test_max_workers_below_start_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="max_workers"):
            AutoscaleSupervisor(lambda w, n, a: [], 3,
                                decision_path=str(tmp_path / "d"),
                                max_workers=2)


# ---------------------------------------------------------------------------
# doctor: evidence correlation
# ---------------------------------------------------------------------------


class TestDoctor:
    SNAP = {
        "slow.0": {"in_backpressure_s": 4.0, "queue_depth": 7.0,
                   "edge0_src_queue_depth": 8.0, "backpressure_s": 0.2,
                   "idle_s": 0.1},
        "sink.0": {"idle_s": 5.0, "queue_depth": 0.0},
        "health": {"slow": 2.0, "job": 2.0},
    }
    EVENTS = [
        ("slow.0", "in_flight", "X", 0.00, 0.040, None),
        ("slow.0", "in_flight", "X", 0.10, 0.050, None),
        ("slow.0", "enqueue", "X", 0.05, 0.001, None),
    ]

    def test_health_findings_rank_breach_first(self):
        from flink_tensorflow_tpu.tracing.doctor import health_findings

        findings = health_findings(self.SNAP, channel_capacity=8)
        assert findings[0]["severity"] == 2
        assert findings[0]["target"].startswith("slow")

    def test_bottleneck_ranking_leads_with_blocked_upstream(self):
        from flink_tensorflow_tpu.tracing.doctor import bottleneck_ranking

        ranked = bottleneck_ranking(self.SNAP)
        assert ranked[0]["operator"] == "slow"
        assert ranked[0]["in_backpressure_s"] == 4.0

    def test_stage_dominance(self):
        from flink_tensorflow_tpu.tracing.doctor import stage_dominance

        stages = stage_dominance(self.EVENTS)
        assert stages["slow"]["stage"] == "in_flight"
        assert stages["slow"]["share"] > 0.9

    def test_diagnose_names_operator_stage_and_action(self):
        from flink_tensorflow_tpu.tracing.doctor import diagnose

        decision = AutoscaleDecision(
            rule_id="edge-queue", target="slow", action="scale_up",
            value=8.0, from_workers=2, to_workers=3, ts=1.0,
            checkpoint_id=4).to_dict()
        report = diagnose(self.SNAP, events=self.EVENTS,
                          decision=decision, channel_capacity=8)
        head = report["findings"][0]
        assert "#1 bottleneck slow" in head
        assert "dominant stage in_flight" in head
        assert any("scale_up 2 -> 3" in f for f in report["findings"])

    def test_diagnose_notes_missing_actuation_on_breach(self):
        from flink_tensorflow_tpu.tracing.doctor import diagnose

        report = diagnose(self.SNAP, channel_capacity=8)
        assert any("no autoscale decision" in f for f in report["findings"])

    CREDIT_SNAP = {
        # The sender is hot (blocked upstream writers) AND its shuffle
        # out-edge spent 2.5s parked at zero credit; the RemoteSink edge
        # on "pipe" carries the operator-scope flavour of the clock.
        "up.0": {"in_backpressure_s": 4.0, "backpressure_s": 3.0,
                 "idle_s": 0.0},
        "shuffle.out.up.0.ch2": {"credit_starved_s": 2.5,
                                 "credits_available": 0.0},
        "shuffle.out.up.0.ch1": {"credit_starved_s": 0.4},
        "pipe.0": {"edge.credit_starved_s": 1.2, "idle_s": 0.1},
        "down.0": {"idle_s": 5.0},
    }

    def test_bottleneck_ranking_carries_credit_evidence(self):
        from flink_tensorflow_tpu.tracing.doctor import bottleneck_ranking

        ranked = {r["operator"]: r
                  for r in bottleneck_ranking(self.CREDIT_SNAP)}
        # Shuffle-plane scopes fold onto their SENDING operator; the
        # worst-starved edge is named so the report can point at the
        # exact link.
        assert ranked["up"]["credit_starved_s"] == pytest.approx(2.9)
        assert ranked["up"]["credit_edge"] == "shuffle.out.up.0.ch2"
        # RemoteSink edges book under their own operator scope.
        assert ranked["pipe"]["credit_starved_s"] == pytest.approx(1.2)
        assert ranked["pipe"]["credit_edge"] == "pipe.0"
        assert ranked["down"]["credit_starved_s"] == 0.0
        assert ranked["down"]["credit_edge"] is None

    def test_diagnose_names_credit_starved_edge(self):
        from flink_tensorflow_tpu.tracing.doctor import diagnose

        report = diagnose(self.CREDIT_SNAP, channel_capacity=8)
        head = report["findings"][0]
        assert "#1 bottleneck up" in head
        assert "credit-starved 2.90s on edge shuffle.out.up.0.ch2" in head
        assert "the jam is below this operator" in head

    def test_cli_round_trip(self, tmp_path):
        from flink_tensorflow_tpu.tracing.doctor import main

        snap_path = str(tmp_path / "snap.json")
        with open(snap_path, "w") as f:
            json.dump(self.SNAP, f)
        out = str(tmp_path / "report.json")
        assert main(["--snapshot", snap_path, "--out", out,
                     "--channel-capacity", "8", "--report-only"]) == 0
        with open(out) as f:
            report = json.load(f)
        assert report["kind"] == "flink-tpu-doctor-report"
        assert report["bottlenecks"][0]["operator"] == "slow"

    def test_cli_unreadable_evidence_exits_2(self, tmp_path):
        from flink_tensorflow_tpu.tracing.doctor import main

        assert main(["--snapshot", str(tmp_path / "absent.json")]) == 2
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as f:
            f.write("ceci n'est pas une decision")
        assert main(["--decision", bad]) == 2


# ---------------------------------------------------------------------------
# the closed-loop soak
# ---------------------------------------------------------------------------


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


@pytest.mark.slow
class TestAutoscaleSoak:
    def test_sustained_breach_drives_one_rescale_byte_identical(
            self, tmp_path):
        """The PR's acceptance demo: a 2-process cohort with a slow keyed
        stage saturates its input queues; the health plane sustains an
        edge-queue BREACH, the actuator (after a completed checkpoint)
        decides 2 -> 3, the supervisor respawns the cohort at 3 with the
        attempt threaded into the fencing epoch, the workers restore
        from the highest complete cohort checkpoint — and the committed
        output equals the fault-free expectation exactly, with exactly
        ONE rescale cycle (max_workers=3 makes a second decision
        at-bounds; hysteresis keeps flapping out)."""
        from flink_tensorflow_tpu.io.files import read_committed

        sys.path.insert(0, os.path.dirname(__file__))
        from _autoscale_worker import NUM_KEYS  # noqa: E402

        worker = os.path.join(os.path.dirname(__file__),
                              "_autoscale_worker.py")
        n, every, par = 1200, 60, 3
        out = str(tmp_path / "out")
        chk = str(tmp_path / "chk")
        decision_path = str(tmp_path / "decision.json")
        ports_by_shape = {2: _free_ports(2), 3: _free_ports(3)}
        pythonpath = os.pathsep.join(
            [os.path.dirname(os.path.dirname(__file__)),
             os.environ.get("PYTHONPATH", "")])

        def command(w, num_workers, attempt):
            return [
                sys.executable, worker, "--index", str(w),
                "--ports", ",".join(map(str, ports_by_shape[num_workers])),
                "--out", out, "--chk", chk, "--n", str(n),
                "--every", str(every), "--par", str(par),
                "--delay", "0.01", "--cap", "8",
                "--epoch", str(attempt),
                "--restore-id", "-1" if attempt == 0 else "-2",
                "--decision", decision_path,
                "--min-workers", "1", "--max-workers", "3",
                "--cooldown", "2.0",
            ]

        sup = AutoscaleSupervisor(
            command, 2, decision_path=decision_path,
            min_workers=1, max_workers=3, max_rescales=2,
            env=lambda w, p, a: {"PYTHONPATH": pythonpath},
            max_restarts=2, poll_s=0.05, kill_grace_s=8.0,
            attempt_timeout_s=150.0,
        )
        outcome = sup.run()

        # Exactly one checkpoint -> rescale -> restore cycle.
        assert outcome.returncode == 0
        assert outcome.attempts == 2
        assert outcome.num_workers == 3
        assert len(outcome.rescales) == 1
        decision = outcome.rescales[0]
        assert decision["action"] == "scale_up"
        assert decision["from_workers"] == 2
        assert decision["to_workers"] == 3
        assert decision["checkpoint_id"] is not None
        assert decision["target"].startswith("slow_sum")

        # Byte-identical exactly-once output: one (key, i, running sum)
        # per record, exactly once, despite the mid-stream rescale.
        sums = {k: 0 for k in range(NUM_KEYS)}
        expected = []
        for i in range(n):
            k = i % NUM_KEYS
            sums[k] += i
            expected.append((k, i, sums[k]))
        got = sorted(
            (int(r.meta["key"]), int(r.meta["i"]), int(r["v"]))
            for r in read_committed(out)
        )
        assert got == sorted(expected)

        # The doctor, fed the supervisor's decision, names the breached
        # rule, the injected bottleneck, and what the supervisor did.
        from flink_tensorflow_tpu.tracing.doctor import diagnose

        report = diagnose(decision["health"].get("targets") and {
            "health": {t: {"OK": 0, "WARN": 1, "BREACH": 2}[s]
                       for t, s in decision["health"]["targets"].items()},
        } or {}, decision=decision, channel_capacity=8)
        assert any("slow_sum" in f for f in report["findings"])
        assert any("scale_up 2 -> 3" in f for f in report["findings"])
