"""Observability layer (src/repro/obs/, DESIGN.md §13).

Covers the metrics registry (bucket edges, quantiles, labeled series,
registration conflicts, enable/disable), the span tracer (nesting, ring
wraparound, Chrome-trace validity, sink export, profiler annotations),
the Prometheus exporter, the device scopes of the train step, and the
three instrumented layers:

  * serving — TTFT/ITL/queue-wait/E2E histograms must agree exactly with
    the per-request timestamps on the GenerationHandles (same clock, same
    emission points), and the per-step ``step_stats`` dict must be
    populated with pool utilization/fragmentation even with obs disabled;
  * training — phase histograms count every step, the sampled full-state
    sync fires on its cadence, ladder/controller decisions land as
    structured events;
  * checkpointing — save/restore/verify durations and byte counters.

The disabled-mode contract is pinned two ways: instruments record
nothing while disabled, and the lowered HLO of a jitted train step is
*bit-identical* with obs enabled vs disabled (the instrumentation is
host-side only and can never alter a traced graph). The device scopes
are compile-time metadata: every op of the compiled step carries one,
and the compiled step differs from an unscoped one in metadata alone.
"""
import contextlib
import dataclasses
import glob
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import tracing
from repro.obs.metrics import MetricsRegistry
from repro.obs.tracing import SpanTracer


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with the process-wide obs state clean
    (disabled, empty series/ring) — obs is global by design."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_histogram_bucket_edges_inclusive():
    r = MetricsRegistry()
    h = r.histogram("h_edges", edges=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1):           # at-or-below the first edge
        h.observe(v)
    h.observe(0.5)                  # (0.1, 1.0]
    h.observe(1.0)                  # edge value lands in its own bucket
    h.observe(99.0)                 # overflow
    s = h.snapshot()["series"][()]
    assert s["buckets"] == [2, 2, 0, 1]
    assert s["count"] == 5
    assert s["min"] == 0.05 and s["max"] == 99.0
    assert s["sum"] == pytest.approx(0.05 + 0.1 + 0.5 + 1.0 + 99.0)


def test_histogram_quantiles():
    r = MetricsRegistry()
    h = r.histogram("h_q", edges=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0):
        h.observe(v)
    # p100 == observed max; p0 clamps to observed min
    assert h.quantile(1.0) == 3.0
    assert h.quantile(0.0) == 0.5
    # median falls inside the (1, 2] bucket, between its two entries
    assert 1.0 <= h.quantile(0.5) <= 2.0
    h.observe(50.0)                 # overflow bucket reports observed max
    assert h.quantile(0.99) == 50.0
    assert h.mean() == pytest.approx((0.5 + 1.5 + 1.5 + 3.0 + 50.0) / 5)
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_labeled_series_tuple_keyed():
    r = MetricsRegistry()
    c = r.counter("c_lbl", labels=("reason",))
    c.inc(1, ("eos",))
    c.inc(2, ("eos",))
    c.inc(1, ("length",))
    assert c.value(("eos",)) == 3
    assert c.value(("length",)) == 1
    assert c.value(("cancelled",)) == 0
    g = r.gauge("g_lbl", labels=("k",))
    g.set(2.0, ("a",))
    g.add(0.5, ("a",))
    assert g.value(("a",)) == 2.5


def test_registration_conflicts_raise():
    r = MetricsRegistry()
    r.counter("m1", labels=("a",))
    assert r.counter("m1", labels=("a",)) is r.get("m1")  # get-or-create
    with pytest.raises(ValueError):
        r.gauge("m1")                           # kind mismatch
    with pytest.raises(ValueError):
        r.counter("m1", labels=("b",))          # label mismatch
    r.histogram("m2", edges=(1.0, 2.0))
    with pytest.raises(ValueError):
        r.histogram("m2", edges=(1.0, 3.0))     # edge mismatch
    with pytest.raises(ValueError):
        r.histogram("m3", edges=(2.0, 1.0))     # non-ascending edges


def test_disabled_registry_records_nothing():
    r = MetricsRegistry(enabled=False)
    c, h = r.counter("c"), r.histogram("h")
    c.inc()
    h.observe(1.0)
    assert c.value() == 0 and h.count() == 0
    r.enable()
    c.inc()
    h.observe(1.0)
    assert c.value() == 1 and h.count() == 1
    r.disable()
    c.inc()
    assert c.value() == 1


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------
def test_span_nesting_depth():
    tr = SpanTracer(capacity=16)
    with tr.span("outer", step=1):
        with tr.span("inner", step=1):
            pass
    recs = tr.records()
    by_name = {r["name"]: r for r in recs}
    assert by_name["outer"]["depth"] == 0
    assert by_name["inner"]["depth"] == 1
    # inner closes first, so it lands in the ring first
    assert [r["name"] for r in recs] == ["inner", "outer"]
    assert all(r["dur"] >= 0 for r in recs)


def test_tracer_ring_wraparound_oldest_first():
    tr = SpanTracer(capacity=4)
    for i in range(7):
        tr.instant("e", step=i)
    assert tr.dropped == 3
    steps = [r["step"] for r in tr.records()]
    assert steps == [3, 4, 5, 6]                # oldest first, newest last
    tr.clear()
    assert tr.records() == [] and tr.dropped == 0


def test_disabled_tracer_is_noop():
    tr = SpanTracer(capacity=4, enabled=False)
    with tr.span("s"):
        pass
    tr.instant("e")
    assert tr.records() == []


def test_chrome_trace_valid():
    tr = SpanTracer(capacity=16)
    with tr.span("phase", step=3, n=2):
        tr.instant("tick", step=3)
    trace = json.loads(json.dumps(tr.chrome_trace()))   # JSON round-trip
    evs = trace["traceEvents"]
    assert len(evs) == 2
    for ev in evs:
        assert ev["ph"] in ("X", "i")
        assert isinstance(ev["ts"], float) and "pid" in ev and "tid" in ev
    x = next(e for e in evs if e["ph"] == "X")
    i = next(e for e in evs if e["ph"] == "i")
    assert x["dur"] >= 0 and x["args"] == {"n": 2, "step": 3}
    assert i["s"] == "t" and i["args"]["step"] == 3


def test_tracer_to_sink_buckets_by_step(tmp_path):
    from repro.telemetry.sink import TelemetrySink

    tr = SpanTracer(capacity=32)
    for step in (1, 2):
        with tr.span("work", step=step):
            pass
    tr.instant("trip", step=2)
    with tr.span("unstepped"):                  # no step -> not exported
        pass
    sink = TelemetrySink(str(tmp_path / "t.jsonl"), every=1)
    assert tr.to_sink(sink) == 3
    sink.close()
    rows = sink.history()
    assert [r["step"] for r in rows] == [1, 2, 2]
    assert "span/work" in rows[0]
    assert rows[2]["event/trip"] == 1.0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
def test_prometheus_exposition_format():
    from repro.obs.exporters import prometheus_exposition

    r = MetricsRegistry()
    r.counter("req_total", "requests", labels=("reason",)).inc(3, ("eos",))
    r.gauge("depth", "queue depth").set(2)
    h = r.histogram("lat_seconds", "latency", edges=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = prometheus_exposition(r)
    lines = text.splitlines()
    assert "# TYPE req_total counter" in lines
    assert '# HELP req_total requests' in lines
    assert 'req_total{reason="eos"} 3' in lines
    assert "depth 2" in lines
    assert "# TYPE lat_seconds histogram" in lines
    # cumulative le buckets ending at +Inf; final bucket == _count
    assert 'lat_seconds_bucket{le="0.1"} 1' in lines
    assert 'lat_seconds_bucket{le="1"} 2' in lines
    assert 'lat_seconds_bucket{le="+Inf"} 3' in lines
    assert "lat_seconds_count 3" in lines
    assert any(line.startswith("lat_seconds_sum ") for line in lines)
    assert text.endswith("\n")


def test_prometheus_rejects_bad_metric_name():
    from repro.obs.exporters import prometheus_exposition

    r = MetricsRegistry()
    r.counter("bad-name")
    with pytest.raises(ValueError):
        prometheus_exposition(r)


def test_prometheus_exporter_atomic_write(tmp_path):
    from repro.obs.exporters import PrometheusExporter

    r = MetricsRegistry()
    r.counter("c_total").inc(5)
    path = tmp_path / "snap" / "metrics.prom"
    out = PrometheusExporter(r, str(path)).write()
    assert out == str(path)
    assert "c_total 5" in path.read_text()
    assert not path.with_suffix(".prom.tmp").exists()


# ---------------------------------------------------------------------------
# telemetry sink: ring wraparound + bucket flush ordering (satellite)
# ---------------------------------------------------------------------------
def test_sink_ring_wraparound(tmp_path):
    from repro.telemetry.sink import TelemetrySink

    sink = TelemetrySink(str(tmp_path / "s.jsonl"), every=1, ring=4)
    for i in range(1, 11):
        sink.log_metrics({"step": i, "loss": float(i)})
    sink.close()
    rows = sink.history()
    assert len(rows) == 4                       # ring capacity
    assert [r["step"] for r in rows] == [7, 8, 9, 10]   # newest last
    # the file keeps everything the ring dropped
    on_disk = [json.loads(ln) for ln in
               (tmp_path / "s.jsonl").read_text().splitlines()]
    assert [r["step"] for r in on_disk] == list(range(1, 11))


def test_sink_bucket_flush_ordering(tmp_path):
    from repro.telemetry.sink import TelemetrySink

    sink = TelemetrySink(str(tmp_path / "s.jsonl"), every=3)
    for i in range(1, 8):                       # 7 records, every=3
        sink.log_metrics({"step": i, "loss": float(i)})
    sink.flush()                                # partial bucket (step 7)
    sink.flush()                                # idempotent: no empty row
    sink.close()
    rows = sink.history()
    # buckets [1..3], [4..6], [7]: step takes the bucket's last value,
    # values aggregate by mean, ordering is strictly by step
    assert [r["step"] for r in rows] == [3, 6, 7]
    assert [r["loss"] for r in rows] == [2.0, 5.0, 7.0]


# ---------------------------------------------------------------------------
# disabled-mode graph bit-identity
# ---------------------------------------------------------------------------
def _tiny_cfg():
    from repro.models.config import ModelConfig

    return ModelConfig(
        name="llama-obs-tiny", family="dense", d_model=32, n_heads=2,
        n_kv_heads=2, d_ff=96, vocab_size=64,
        schedule=((("attn",), 2),), param_dtype="float32",
        compute_dtype="float32", remat=False, q_chunk=16, kv_chunk=16)


def test_obs_toggle_keeps_train_step_hlo_bit_identical():
    """Enabling obs must not alter any traced graph: the instrumentation
    is host-side only. Pinned by lowering the same train step with obs
    disabled and enabled and comparing the HLO text byte-for-byte."""
    from repro.models import transformer as T
    from repro.optim.api import get_optimizer
    from repro.train.steps import TrainState, make_train_step

    cfg = _tiny_cfg()
    opt = get_optimizer("dct_adamw", lr=1e-3, rank=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    state = TrainState(jnp.zeros((), jnp.int32), params, opt.init(params))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "targets": jnp.zeros((2, 16), jnp.int32)}
    step = make_train_step(cfg, opt)

    obs.disable()
    hlo_off = jax.jit(step).lower(state, batch).as_text()
    obs.enable()
    hlo_on = jax.jit(step).lower(state, batch).as_text()
    assert hlo_off == hlo_on


# ---------------------------------------------------------------------------
# device scopes: every op of the compiled train step names its layer
# ---------------------------------------------------------------------------
SCOPES = ("model/embed", "model/blocks", "model/attn", "model/mlp",
          "model/head", "step/clip", "step/apply", "opt/update", "opt/ef",
          "opt/select_project", "opt/adam", "opt/backproject",
          "opt/full_adam")
# instructions that run no device op of their own
_STRUCTURE = ("parameter", "constant", "get-tuple-element", "tuple",
              "bitcast", "while", "conditional", "call")


def _compiled_step_hlo(fused: str) -> str:
    """A tiny rematerialised dct_adamw step, compiled (the Pallas kernels
    in interpret mode for ``fused="on"``)."""
    from repro.models import transformer as T
    from repro.optim.api import get_optimizer
    from repro.train.steps import TrainState, make_train_step

    cfg = dataclasses.replace(_tiny_cfg(), remat=True)
    opt = get_optimizer("dct_adamw", lr=1e-3, rank=8, fused=fused)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    state = TrainState(jnp.zeros((), jnp.int32), params, opt.init(params))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "targets": jnp.zeros((2, 16), jnp.int32)}
    step = jax.jit(make_train_step(cfg, opt))
    return step.lower(state, batch).compile().as_text()


@pytest.mark.parametrize("fused", ["on", "off"])
def test_every_compiled_step_op_carries_a_scope(fused):
    from bench import scopes

    ops = {k: v for k, v in scopes.hlo_ops(_compiled_step_hlo(fused)).items()
           if v.opcode not in _STRUCTURE}
    # every op the program traced (a name-stack op_name) is in a scope
    traced = {k: v for k, v in ops.items() if "/" in v.op_name}
    bare = sorted(f"{k}: {v.op_name}" for k, v in traced.items()
                  if scopes.scope_of(v.op_name) is None)
    assert len(bare) < 0.05 * len(traced), bare
    # and the compiler's own ops (copies) take the scope they serve
    rest = sorted(f"{k}: {v.opcode}" for k, v in ops.items()
                  if v.scope is None)
    assert len(rest) < 0.05 * len(ops), rest
    assert set(SCOPES) <= {v.scope for v in ops.values()}


def _numbered_in_order(hlo_text: str) -> str:
    """Instruction names replaced by their order of first use: a second
    lowering in one process numbers some instructions differently."""
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%i{len(names)}"),
                  hlo_text)


def test_scopes_change_only_the_metadata(monkeypatch):
    """The compiled step with its scopes equals, instruction for
    instruction, the step compiled with every scope a no-op."""
    from bench import scopes

    scoped = _compiled_step_hlo("off")
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compiled_step_hlo("off")
    assert "model/attn" in scoped and "model/attn" not in bare
    assert _numbered_in_order(scopes.strip_metadata(scoped)) == \
        _numbered_in_order(scopes.strip_metadata(bare))


def _host_events(trace_dir) -> list:
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(path)
    return [e for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


def test_enabled_span_is_a_profiler_annotation(tmp_path):
    on = SpanTracer(capacity=4)
    off = SpanTracer(capacity=4, enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    with on.span("obs-test/enabled"):
        pass
    with off.span("obs-test/disabled"):
        pass
    jax.profiler.stop_trace()
    names = [e.name for e in _host_events(tmp_path)]
    assert names.count("obs-test/enabled") == 1
    assert "obs-test/disabled" not in names
    assert off.span("x") is tracing._NOOP
    (rec,) = on.records()
    assert rec["name"] == "obs-test/enabled"


def test_trainer_spans_and_steps_reach_the_profiler(tmp_path):
    from repro.data.synthetic import SyntheticLM
    from repro.models import transformer as T
    from repro.optim.api import get_optimizer
    from repro.train.loop import Trainer
    from repro.train.steps import TrainState, make_train_step

    cfg = _tiny_cfg()
    opt = get_optimizer("adamw", lr=1e-3)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16,
                     global_batch=2, seed=0)

    def trainer():
        return Trainer(
            train_step=jax.jit(make_train_step(cfg, opt)),
            init_state_fn=lambda: TrainState(jnp.zeros((), jnp.int32),
                                             params, opt.init(params)),
            batch_fn=lambda i: ds.batch(jnp.int32(i)),
            log_fn=lambda s: None)

    trainer().run(1, resume=False)           # compiles outside the trace
    obs.enable()
    jax.profiler.start_trace(str(tmp_path / "on"))
    trainer().run(3, resume=False)
    jax.profiler.stop_trace()
    obs.disable()
    events = _host_events(tmp_path / "on")
    names = [e.name for e in events]
    for span in ("train/data_wait", "train/dispatch", "train/host_sync"):
        assert names.count(span) == 3, span
    steps = [e for e in events if e.name == "train"]
    assert len(steps) == 3
    # each phase lies inside its step's marker, on one clock
    for e in events:
        if e.name == "train/dispatch":
            assert any(s.start_ns <= e.start_ns and e.end_ns <= s.end_ns
                       for s in steps)
    # with obs off the loop leaves nothing in a trace
    jax.profiler.start_trace(str(tmp_path / "off"))
    trainer().run(2, resume=False)
    jax.profiler.stop_trace()
    names = {e.name for e in _host_events(tmp_path / "off")}
    assert not names & {"train", "train/data_wait", "train/dispatch"}


# ---------------------------------------------------------------------------
# serving instrumentation
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def paged_setup():
    from repro.configs.registry import SMOKES
    from repro.models import transformer as T

    cfg = SMOKES["qwen2.5-32b"]
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _churn(cfg, params, *, cancel_one: bool = False):
    from repro.serve import PagedServeEngine, Session

    eng = PagedServeEngine(cfg, params, block_size=4, num_blocks=32,
                           max_blocks_per_seq=6, num_slots=2,
                           max_prefill_len=16, prefill_chunk=8,
                           num_splits=2)
    sess = Session(eng, "obs")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,))
               for n in (9, 5, 11, 7, 10)]
    budgets = [6, 3, 5, 4, 4]
    hs = [sess.submit(prompts[0], max_new_tokens=budgets[0]),
          sess.submit(prompts[1], max_new_tokens=budgets[1])]
    eng.step(); eng.step()
    hs.append(sess.submit(prompts[2], max_new_tokens=budgets[2]))
    hs.append(sess.submit(prompts[3], max_new_tokens=budgets[3]))
    if cancel_one:
        # a 5th request queues behind the two busy slots while hs[2] is
        # cancelled before it was ever admitted
        hs.append(sess.submit(prompts[4], max_new_tokens=budgets[4]))
        hs[2].cancel()
    eng.run()
    return eng, hs


def test_serve_histograms_match_handle_timestamps(paged_setup):
    """The acceptance invariant: TTFT/ITL/queue-wait/E2E histograms from
    a churn run agree with the per-request timestamps on the handles —
    same count, same sum (the engine emits both from the same perf_counter
    stamps at the same step boundaries, quantized to whole decode steps)."""
    cfg, params = paged_setup
    obs.enable()
    eng, hs = _churn(cfg, params)
    assert all(h.done for h in hs)
    r = obs.registry()

    ttfts = [h.ttft for h in hs]
    itls = [g for h in hs for g in h.inter_token_latencies()]
    e2es = [h.e2e for h in hs]
    qw = [h.queue_wait for h in hs]
    for name, vals in (("serve_ttft_seconds", ttfts),
                       ("serve_itl_seconds", itls),
                       ("serve_queue_wait_seconds", qw),
                       ("serve_e2e_seconds", e2es)):
        hist = r.get(name)
        assert hist.count() == len(vals), name
        assert hist.sum() == pytest.approx(sum(vals), rel=1e-9), name
    # every inter-token gap is a whole number of decode steps: positive,
    # and bounded by the run's wall time
    assert all(g > 0 for g in itls)
    for h in hs:
        assert h.ttft >= h.queue_wait > 0
        assert h.e2e >= h.token_times[-1] - h.t_submit
    assert r.get("serve_tokens_total").value() == \
        sum(len(h.tokens) for h in hs)
    assert r.get("serve_requests_submitted_total").value() == 4
    assert r.get("serve_requests_finished_total").value(("length",)) == 4


def test_serve_step_stats_without_obs(paged_setup):
    """Satellite: allocator utilization/fragmentation ride the engine's
    per-step stats dict with obs fully disabled."""
    cfg, params = paged_setup
    assert not obs.enabled()
    eng, hs = _churn(cfg, params)
    st = eng.step_stats
    for key in ("step", "running", "pending", "tokens_emitted",
                "used_blocks", "free_blocks", "utilization",
                "fragmentation"):
        assert key in st, key
    assert st["running"] == 0 and st["pending"] == 0
    assert st["free_blocks"] == 32 and st["used_blocks"] == 0
    assert st["tokens_emitted"] == sum(len(h.tokens) for h in hs)
    assert eng.stats()["tokens_emitted"] == st["tokens_emitted"]
    # and nothing leaked into the disabled registry
    assert obs.registry().get("serve_tokens_total").value() == 0


def test_serve_cancel_and_backpressure_counters(paged_setup):
    cfg, params = paged_setup
    obs.enable()
    eng, hs = _churn(cfg, params, cancel_one=True)
    r = obs.registry()
    cancels = r.get("serve_cancellations_total")
    assert cancels.value(("queued",)) + cancels.value(("running",)) == 1
    fin = r.get("serve_requests_finished_total")
    assert fin.value(("cancelled",)) == 1
    assert fin.value(("length",)) == 4
    # 5 submissions through 2 slots -> someone waited on a slot at least
    # one step boundary
    assert r.get("serve_backpressure_steps_total").value(("slots",)) \
        + r.get("serve_backpressure_steps_total").value(("blocks",)) > 0
    # gauges settle at drained-pool values
    assert r.get("serve_slots_active").value() == 0
    assert r.get("serve_pool_free_blocks").value() == 32
    assert r.get("serve_pool_utilization").value() == 0.0
    # spans from admit/decode are in the ring with step tags
    names = {rec["name"] for rec in obs.tracer().records()}
    assert "serve/admit" in names and "serve/decode_step" in names


# ---------------------------------------------------------------------------
# training instrumentation
# ---------------------------------------------------------------------------
def test_trainer_phase_metrics_and_sampled_sync(tmp_path):
    from repro.data.synthetic import SyntheticLM
    from repro.models import transformer as T
    from repro.optim.api import get_optimizer
    from repro.train.loop import Trainer
    from repro.train.steps import TrainState, make_train_step

    cfg = _tiny_cfg()
    opt = get_optimizer("adamw", lr=1e-3)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16,
                     global_batch=2, seed=0)
    obs.enable()
    trainer = Trainer(
        train_step=jax.jit(make_train_step(cfg, opt)),
        init_state_fn=lambda: TrainState(jnp.zeros((), jnp.int32), params,
                                         opt.init(params)),
        batch_fn=lambda i: ds.batch(jnp.int32(i)),
        log_fn=lambda s: None, sync_sample_every=2)
    trainer.run(5, resume=False)
    r = obs.registry()
    for name in ("train_data_wait_seconds", "train_dispatch_seconds",
                 "train_host_sync_seconds", "train_step_seconds"):
        assert r.get(name).count() == 5, name
    assert r.get("train_full_sync_seconds").count() == 2   # steps 2, 4
    assert r.get("train_steps_total").value(("committed",)) == 5
    assert r.get("train_full_sync_seconds").sum() > 0
    names = [rec["name"] for rec in obs.tracer().records()]
    for span in ("train/data_wait", "train/dispatch", "train/host_sync",
                 "train/full_sync"):
        assert span in names, span


def test_resilience_ladder_events():
    from repro.train.resilience import ResilienceConfig, ResilienceManager

    obs.enable()
    rm = ResilienceManager(ResilienceConfig(max_skips=1, max_rollbacks=1),
                           log_fn=lambda s: None)
    assert rm.observe(1, 1.0, True).kind == "ok"
    assert rm.observe(2, float("nan"), False).kind == "skip"
    assert rm.observe(3, float("nan"), False).kind == "rollback"
    rm.rolled_back(from_step=3, to_step=0)
    assert rm.observe(4, float("nan"), False).kind == "skip"
    assert rm.observe(5, float("nan"), False).kind == "halt"
    r = obs.registry()
    assert r.get("resilience_guard_trips_total").value() == 4
    acts = r.get("resilience_actions_total")
    assert acts.value(("skip",)) == 2
    assert acts.value(("rollback",)) == 1
    assert acts.value(("halt",)) == 1
    names = [rec["name"] for rec in obs.tracer().records()]
    assert names.count("resilience/guard_trip") == 4
    assert "resilience/rollback" in names and "resilience/halt" in names
    halt = next(rec for rec in obs.tracer().records()
                if rec["name"] == "resilience/halt")
    assert "reason" in halt["args"] and halt["args"]["rollbacks"] == 2


def test_controller_events_carry_before_after():
    from repro.telemetry.controllers import (LeafInfo, RankAllocator,
                                             RankAllocatorConfig,
                                             RefreshScheduler,
                                             RefreshSchedulerConfig)

    obs.enable()
    leaves = {"a": LeafInfo(rows=64, cols=64),
              "b": LeafInfo(rows=64, cols=64)}
    ra = RankAllocator(RankAllocatorConfig(base_rank=16, quantum=8,
                                           decide_every=1), leaves)
    ra.observe(1, {"a": {"captured_energy": 0.99},
                   "b": {"captured_energy": 0.30}})
    new = ra.propose(2)
    assert new is not None and new["b"] > new["a"]
    r = obs.registry()
    assert r.get("controller_rank_reallocations_total").value() == 1
    assert r.get("controller_ranks_changed_total").value() == \
        sum(1 for p in new if new[p] != min(16, leaves[p].cols))
    ev = next(rec for rec in obs.tracer().records()
              if rec["name"] == "controller/rank_realloc")
    changed = ev["args"]["changed"]
    assert all({"before", "after"} <= set(v) for v in changed.values())

    rs = RefreshScheduler(RefreshSchedulerConfig(decide_every=1,
                                                 cooldown=0), ["a"])
    rs.observe(1, {"a": {"index_overlap": 0.99}})    # low drift -> stretch
    assert rs.propose(2) == {"a": 2}
    assert r.get("controller_interval_changes_total").value() == 1
    ev = next(rec for rec in obs.tracer().records()
              if rec["name"] == "controller/interval_change")
    assert ev["args"]["changed"]["a"]["before"] == 1
    assert ev["args"]["changed"]["a"]["after"] == 2


# ---------------------------------------------------------------------------
# checkpoint instrumentation
# ---------------------------------------------------------------------------
def test_checkpoint_durations_and_bytes(tmp_path):
    from repro.train.checkpoint import (CheckpointCorruptError,
                                        CheckpointManager)

    obs.enable()
    state = {"w": jnp.arange(64, dtype=jnp.float32),
             "b": jnp.ones((8,), jnp.float32)}
    nbytes = 64 * 4 + 8 * 4
    mgr = CheckpointManager(str(tmp_path / "ckpt"), log=lambda s: None)
    mgr.save(1, state)
    mgr.verify(1)
    restored = mgr.restore(1, state)
    assert jnp.array_equal(restored["w"], state["w"])
    r = obs.registry()
    assert r.get("ckpt_saves_total").value() == 1
    assert r.get("ckpt_restores_total").value() == 1
    assert r.get("ckpt_bytes_written_total").value() == nbytes
    assert r.get("ckpt_bytes_read_total").value() == nbytes
    assert r.get("ckpt_save_seconds").count() == 1
    assert r.get("ckpt_verify_seconds").count() == 1
    assert r.get("ckpt_restore_seconds").count() == 1
    assert r.get("ckpt_save_seconds").sum() > 0

    # corruption: flip bytes in state.npz -> verify raises + counter
    p = tmp_path / "ckpt" / "step_1" / "state.npz"
    raw = bytearray(p.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    p.write_bytes(bytes(raw))
    with pytest.raises(CheckpointCorruptError):
        mgr.verify(1)
    assert r.get("ckpt_corruptions_total").value() == 1
    names = {rec["name"] for rec in obs.tracer().records()}
    assert {"ckpt/write", "ckpt/verify", "ckpt/restore",
            "ckpt/corrupt"} <= names
