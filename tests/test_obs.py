"""Telemetry layer (repro.obs): tracer, registry, and the instrumented
serve/graph/dock layers — including the disabled-mode overhead contract
and greedy bit-identity with tracing ON."""
import glob
import json
import subprocess
import sys
import threading
import warnings
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import RLConfig
from repro.core.rollout import RolloutEngine
from repro.core.trainer import GRPOTrainer, build_grpo_graph
from repro.core.transfer_dock import (META_PER_SAMPLE, META_SCALAR_BYTES,
                                      CentralReplayBuffer, DispatchLedger,
                                      TransferDock)
from repro.data.prompts import PromptDataset, pattern_task
from repro.data.tokenizer import ByteTokenizer
from repro.models.model import build_model
from repro.obs import NULL_SPAN, MetricsRegistry, Tracer, get_tracer
from repro.obs import tracer as tracer_mod
from repro.serve.engine import ServingEngine

ROOT = Path(__file__).resolve().parents[1]
TOK = ByteTokenizer()
GRPO_NODES = [n.name for n in build_grpo_graph().nodes]


class CountingTracer(Tracer):
    """Probe: counts every event that reaches the sink (the one place all
    spans/instants/counters land), so "disabled => nothing appended" is a
    checkable number rather than a hope."""

    def __init__(self, enabled=False):
        super().__init__(enabled)
        self.appends = 0

    def _append(self, ev):
        self.appends += 1
        super()._append(ev)


@pytest.fixture
def annotations(monkeypatch):
    """Probe: counts the profiler annotations the tracer creates."""
    made = []

    class Counting(tracer_mod.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(tracer_mod, "TraceAnnotation", Counting)
    return made


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("yi-6b").replace(dtype="float32", remat=False)
    m = build_model(cfg)
    params = m.init(cfg, jax.random.PRNGKey(0))
    return cfg, m, params


def _prompts(b, pl, seed=0):
    return np.random.RandomState(seed).randint(0, 250, (b, pl)).astype(np.int32)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_span_nesting_and_order():
    tr = Tracer(enabled=True)
    with tr.span("outer", cat="t"):
        with tr.span("inner", cat="t"):
            pass
        tr.instant("mark", cat="t")
    evs = tr.events
    assert [e["name"] for e in evs] == ["inner", "mark", "outer"]  # exit order
    outer = evs[2]
    inner = evs[0]
    # containment: the exporter's ts sort restores timeline order, and
    # Perfetto reconstructs nesting from interval containment
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    sorted_names = [e["name"] for e in tr.to_chrome()["traceEvents"]]
    assert sorted_names == ["outer", "inner", "mark"]


def test_span_args_mutable_until_exit():
    tr = Tracer(enabled=True)
    with tr.span("s", args=(args := {})):
        args["late"] = 1
    assert tr.events[0]["args"] == {"late": 1}


def test_concurrent_spans_get_distinct_tids():
    tr = Tracer(enabled=True)
    barrier = threading.Barrier(4)

    def work(i):
        barrier.wait()
        with tr.span(f"w{i}", cat="t"):
            pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events
    assert sorted(e["name"] for e in evs) == ["w0", "w1", "w2", "w3"]
    assert len({e["tid"] for e in evs}) == 4          # one track per thread
    assert all(e["pid"] == 0 for e in evs)


def test_disabled_tracer_is_contractually_free():
    tr = CountingTracer(enabled=False)
    # span: the module singleton, no allocation per call
    s1 = tr.span("a", cat="x", args={"k": 1})
    s2 = tr.span("b")
    assert s1 is NULL_SPAN and s2 is NULL_SPAN
    with s1:
        pass
    tr.instant("i", args={"k": 1})
    tr.counter("c", {"v": 3})
    assert tr.appends == 0
    assert tr.events == []
    # the process-default tracer ships disabled
    assert not get_tracer().enabled


def test_exporter_chrome_schema(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("s", cat="c", args={"n": 1}):
        tr.instant("i")
    tr.counter("cnt", {"a": 1, "b": 2})
    path = tr.export(str(tmp_path / "t.trace.json"))
    doc = json.load(open(path))
    assert set(doc) == {"traceEvents", "displayTimeUnit", "profilerEpochNs"}
    assert doc["profilerEpochNs"] == tr._epoch_wall_ns > 0
    evs = doc["traceEvents"]
    assert len(evs) == 3
    for ev in evs:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
        assert ev["ts"] >= 0
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)                            # exporter sorts
    x = [e for e in evs if e["ph"] == "X"]
    assert len(x) == 1 and x[0]["dur"] >= 0
    c = [e for e in evs if e["ph"] == "C"]
    assert c[0]["args"] == {"a": 1, "b": 2}


def test_tracer_clear_and_enable_toggle():
    tr = Tracer()
    tr.enable()
    tr.instant("i")
    assert len(tr.events) == 1
    tr.disable()
    tr.instant("j")
    assert len(tr.events) == 1
    tr.clear()
    assert tr.events == []


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_nearest_rank_percentiles():
    m = MetricsRegistry()
    for v in range(1, 101):
        m.observe("lat", v)
    s = m.summarize("lat")
    assert (s["p50"], s["p90"], s["p95"], s["p99"]) == (50, 90, 95, 99)
    assert s["count"] == 100 and s["min"] == 1 and s["max"] == 100
    assert m.percentile("lat", 0.5) == 50
    assert m.percentile("nope", 0.5) is None
    assert m.summarize("nope") == {}


def test_registry_snapshot_stable_and_serializable():
    m = MetricsRegistry()
    m.inc("b", 2)
    m.inc("a")
    m.set("g", 1.5)
    m.set_max("hw", 3)
    m.set_max("hw", 1)                                 # must not regress
    m.observe("h", 0.25)
    s1, s2 = m.snapshot(), m.snapshot()
    assert s1 == s2                                    # no writes => equal
    json.dumps(s1)                                     # serializable
    assert list(s1["counters"]) == ["a", "b"]          # sorted keys
    assert s1["gauges"]["hw"] == 3
    assert m.value("a") == 1 and m.value("missing", -1) == -1
    m.clear()
    assert m.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


# ---------------------------------------------------------------------------
# dock ledger: record_meta msgs contract (satellite)
# ---------------------------------------------------------------------------

def test_record_meta_msgs_contract():
    """PUT broadcasts one latency-bearing message per controller; a
    TransferDock metadata GET is co-located (msgs=0, bytes still counted);
    the CentralReplayBuffer baseline pays one real RPC per GET (plus
    cross-node bytes for workers off node 0).  This asymmetry is the
    paper's metadata-locality argument — pinned so nobody "fixes" it."""
    states = {"a": 0, "b": 1}
    dock = TransferDock(2, states, DispatchLedger())
    dock.put("f", [0, 1], np.zeros((2, 4), np.float32), src_node=0)
    assert dock.ledger.metadata_msgs == len(states)    # broadcast: msgs=nctl

    before_b, before_m = dock.ledger.metadata_bytes, dock.ledger.metadata_msgs
    dock.request_metadata("b", ["f"])                  # worker on node 1
    assert dock.ledger.metadata_msgs == before_m       # intranode: msgs=0
    assert dock.ledger.metadata_bytes == before_b + (
        META_PER_SAMPLE * META_SCALAR_BYTES)           # bytes still counted

    crb = CentralReplayBuffer(states, DispatchLedger())
    crb.put("f", [0, 1], np.zeros((2, 4), np.float32), src_node=0)
    m0, x0 = crb.ledger.metadata_msgs, crb.ledger.internode_bytes
    crb.request_metadata("a", ["f"])                   # worker ON node 0
    assert crb.ledger.metadata_msgs == m0 + 1          # real RPC: msgs=1
    assert crb.ledger.internode_bytes == x0            # but no cross bytes
    crb.request_metadata("b", ["f"])                   # worker OFF node 0
    assert crb.ledger.metadata_msgs == m0 + 2
    assert crb.ledger.internode_bytes == x0 + (
        META_PER_SAMPLE * META_SCALAR_BYTES)           # crosses the network


def test_ledger_emits_dock_counter_events():
    tr = Tracer(enabled=True)
    led = DispatchLedger(tracer=tr)
    led.record(100, cross=True, node=1)
    led.record(50, cross=False)
    led.record_meta(12, msgs=3)
    names = [e["name"] for e in tr.events]
    assert names == ["dock.bytes", "dock.bytes", "dock.metadata"]
    assert tr.events[1]["args"] == {"internode": 100, "intranode": 50}
    assert tr.events[2]["args"] == {"bytes": 12, "msgs": 3}
    assert all(e["ph"] == "C" and e["cat"] == "dock" for e in tr.events)


# ---------------------------------------------------------------------------
# serving engine: stats(), step telemetry, overhead + bit-identity
# ---------------------------------------------------------------------------

def test_engine_stats_and_step_telemetry(setup):
    cfg, _, params = setup
    tr = Tracer(enabled=True)
    eng = ServingEngine(cfg, max_new=6, eos_id=TOK.eos_id, pad_id=TOK.pad_id,
                        greedy=True, max_slots=2, block_size=4, tracer=tr)
    prompts = _prompts(3, 8)
    for p in prompts:
        eng.submit(p)
    outs = eng.drain(params)
    st = eng.stats()
    assert st["submitted"] == 3 and st["finished"] == len(outs) == 3
    assert st["steps"] == eng.steps > 0
    assert st["prefill_tokens"] == eng.prefill_tokens > 0
    assert st["decode_tokens"] > 0
    assert st["ttft_s"]["count"] == 3 and st["latency_s"]["count"] == 3
    assert st["ttft_s"]["p50"] <= st["latency_s"]["max"]

    evs = tr.events
    steps = [e for e in evs if e["name"] == "serve.step"]
    assert len(steps) == st["steps"]
    assert all(e["ph"] == "X" and e["cat"] == "serve" for e in steps)
    assert {"step", "live_slots", "waiting", "prefill_tokens",
            "finished"} <= set(steps[0]["args"])
    # cumulative token counters: one sample per step, final == registry
    tok_samples = [e for e in evs if e["name"] == "serve.tokens"]
    assert len(tok_samples) == st["steps"]
    assert tok_samples[-1]["args"]["prefill"] == st["prefill_tokens"]
    assert tok_samples[-1]["args"]["decode"] == st["decode_tokens"]
    # scheduler lifecycle instants on the same timeline
    inames = {e["name"] for e in evs if e["ph"] == "i"}
    assert {"serve.admit", "serve.finish"} <= inames
    fin = [e for e in evs if e["name"] == "serve.finish"]
    assert len(fin) == 3 and all("rid" in e["args"] for e in fin)


def test_generate_bitcompat_with_tracer_enabled(setup):
    """The acceptance property survives tracing: greedy ServingEngine with
    an ENABLED tracer is still token- and logp-identical to the sync
    engine (instrumentation changed the schedule's visibility, not math)."""
    cfg, _, params = setup
    b, pl, mn = 4, 8, 12        # S == B, block-aligned (the bitwise scope)
    prompts = _prompts(b, pl, seed=2)
    sync = RolloutEngine(cfg, max_new=mn, eos_id=TOK.eos_id,
                         pad_id=TOK.pad_id, greedy=True)
    tr = Tracer(enabled=True)
    cont = ServingEngine(cfg, max_new=mn, eos_id=TOK.eos_id, pad_id=TOK.pad_id,
                         greedy=True, max_slots=b, block_size=4, tracer=tr)
    r1 = sync.generate(params, prompts, jax.random.PRNGKey(5))
    r2 = cont.generate(params, prompts, jax.random.PRNGKey(5))
    np.testing.assert_array_equal(r1.tokens, r2.tokens)
    np.testing.assert_array_equal(r1.response_mask, r2.response_mask)
    np.testing.assert_array_equal(r1.gen_logp, r2.gen_logp)
    assert any(e["name"] == "serve.step" for e in tr.events)


@pytest.mark.parametrize("greedy", [True, False])
def test_disabled_tracer_adds_nothing_to_serving_steps(setup, annotations,
                                                       greedy):
    """Overhead guard: a full serving run with the tracer disabled must
    append ZERO events, allocate ZERO span objects (every span() call
    returns the module singleton) and create ZERO profiler annotations —
    counter-based, immune to CPU noise."""
    cfg, _, params = setup
    tr = CountingTracer(enabled=False)
    eng = ServingEngine(cfg, max_new=6, eos_id=TOK.eos_id, pad_id=TOK.pad_id,
                        greedy=greedy, max_slots=2, block_size=4, tracer=tr)
    for p in _prompts(3, 8, seed=4):
        eng.submit(p)
    outs = eng.drain(params)
    assert len(outs) == 3
    assert tr.appends == 0 and tr.events == []
    assert annotations == []
    assert eng.tracer.span("probe") is NULL_SPAN
    # the registry keeps counting regardless — stats() is always available
    assert eng.stats()["finished"] == 3


def test_enabled_tracer_creates_one_annotation_per_span(setup, annotations):
    """The probe above sees annotations when there are some: each span of
    an enabled tracer creates exactly one, of its own name."""
    cfg, _, params = setup
    tr = Tracer(enabled=True)
    eng = ServingEngine(cfg, max_new=4, eos_id=TOK.eos_id, pad_id=TOK.pad_id,
                        greedy=False, max_slots=2, block_size=4, tracer=tr)
    for p in _prompts(2, 8, seed=6):
        eng.submit(p)
    eng.drain(params)
    spans = [e["name"] for e in tr.events if e["ph"] == "X"]
    assert sorted(annotations) == sorted(spans)
    assert {"serve.submit", "serve.step", "serve.admit", "serve.prefill",
            "serve.first_token", "serve.decode.prep", "serve.decode.launch",
            "serve.decode.wait", "serve.retire"} == set(spans)


def _xplane_spans(trace_dir):
    """(name, start on the profiler's clock, end, stats) of every host
    event of the trace, and the trace's start on that clock."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    assert path, "the profiler wrote no trace"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        pd = ProfileData.from_file(path[-1])
        planes = list(pd.planes)
        t0 = [dict(p.stats)["profile_start_time"] for p in planes
              if p.name == "Task Environment"]
        assert len(t0) == 1
        out = []
        for plane in planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        out.append((ev.name, ev.start_ns + t0[0],
                                    ev.start_ns + ev.duration_ns + t0[0],
                                    dict(ev.stats)))
    return out


def test_enabled_spans_reach_the_profiler_trace(setup, tmp_path):
    """An enabled tracer's spans land in a JAX profiler trace (CPU here),
    nested as they ran — ``serve.decode.wait`` inside ``serve.step`` — with
    the prefill's ``rid`` as a stat, and the Chrome export's
    ``profilerEpochNs`` puts each span within 1 ms of its xplane start."""
    cfg, _, params = setup
    tr = Tracer(enabled=True)
    eng = ServingEngine(cfg, max_new=4, eos_id=TOK.eos_id, pad_id=TOK.pad_id,
                        greedy=False, max_slots=2, block_size=4, tracer=tr)
    eng.submit(_prompts(1, 8, seed=7)[0])
    eng.drain(params)                  # compiles outside the trace
    tr.clear()
    s0 = eng.steps
    jax.profiler.start_trace(str(tmp_path))
    try:
        for p in _prompts(2, 8, seed=8):
            eng.submit(p)
        eng.drain(params)
    finally:
        jax.profiler.stop_trace()
    xs = [x for x in _xplane_spans(tmp_path) if x[0].startswith("serve.")]
    steps = [x for x in xs if x[0] == "serve.step"]
    waits = [x for x in xs if x[0] == "serve.decode.wait"]
    assert len(steps) == eng.steps - s0 and len(waits) == len(steps)
    for _, s, e, _ in waits:
        assert any(s0 <= s and e <= e0 for _, s0, e0, _ in steps)
    rids = sorted(x[3]["rid"] for x in xs if x[0] == "serve.prefill")
    assert rids == [1, 2]
    doc = tr.to_chrome()
    chrome = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert sorted(e["name"] for e in chrome) == sorted(x[0] for x in xs)
    starts = {}
    for name, s, _, _ in xs:
        starts.setdefault(name, []).append(s)
    for e in chrome:
        at = e["ts"] * 1e3 + doc["profilerEpochNs"]
        assert min(abs(at - s) for s in starts[e["name"]]) < 1e6


def _check_launch_and_host_read_counts(cfg, params, greedy):
    n, mn = 3, 5
    eng = ServingEngine(cfg, max_new=mn, eos_id=cfg.vocab_size + 7,
                        pad_id=TOK.pad_id, greedy=greedy, max_slots=n,
                        block_size=4)
    for p in _prompts(n, 8, seed=9):
        eng.submit(p)
    eng.step(params)
    eng.metrics.snapshot()        # a snapshot mid-run reads no counted value
    outs = eng.drain(params)
    assert sorted(len(o.gen) for o in outs) == [mn] * n
    st = eng.stats()
    steps = mn - 1
    assert st["steps"] == steps and st["prefill_tokens"] == 8 * n
    per_submit = 0 if greedy else 1
    per_step = 1 if greedy else 2
    assert st["launches"] == n * (per_submit + 4) + steps * per_step
    assert st["host_reads"] == n * (per_submit + 2) + steps * 2
    return eng


@pytest.mark.parametrize("greedy", [True, False])
def test_launch_and_host_read_counts(setup, greedy):
    """Exact ``serve.launches`` / ``serve.host_reads`` of a run of known
    shape: n distinct prompts admitted in the first step, every request
    running to ``max_new`` (the EOS id lies outside the vocabulary).  Per
    submit (sampled): the stream, 1 launch + 1 read; per admission: the
    prefill, two pool writes and the first-token draw, 4 launches + 2
    reads; per decode step: ``_step`` (+ ``_draw`` sampled) and 2 reads."""
    cfg, _, params = setup
    _check_launch_and_host_read_counts(cfg, params, greedy)


@pytest.mark.parametrize("greedy", [True, False])
def test_moe_counts_add_no_launch_or_host_read(greedy):
    """An MoE engine, holding 2 of its 4 experts, launches and reads as
    the dense one does: its ``serve.moe.*`` counts ride the programs it
    runs anyway and are read by ``snapshot()`` alone.  Each of the 3
    admissions and 4 decode steps applies the 2 expert layers once."""
    cfg = get_smoke_config("qwen3-moe-30b").replace(
        dtype="float32", remat=False, moe_held=(2, 2))
    params = build_model(cfg).init(cfg, jax.random.PRNGKey(0))
    eng = _check_launch_and_host_read_counts(cfg, params, greedy)
    c = eng.metrics.snapshot()["counters"]
    assert c["serve.moe.calls"] == 2 * (3 + 4)
    assert 0 < c["serve.moe.rows"] <= c["serve.moe.padded_rows"]


# ---------------------------------------------------------------------------
# trainer end-to-end: graph spans, dock counters, export + report CLI
# ---------------------------------------------------------------------------

def test_trainer_trace_end_to_end(tmp_path):
    cfg = get_smoke_config("yi-6b").replace(dtype="float32", remat=False)
    rl = RLConfig(num_generations=2, max_prompt_len=12, max_response_len=8,
                  rollout_engine="serving", serve_max_slots=2,
                  serve_block_size=4,
                  trace_path=str(tmp_path / "run.trace.json"))
    ds = PromptDataset(pattern_task(), max_prompt_len=rl.max_prompt_len,
                       seed=0)
    trainer = GRPOTrainer(cfg, rl, ds, num_nodes=2, seed=0)
    assert trainer.tracer.enabled                      # trace_path enables it
    stats = trainer.iteration(2)

    evs = trainer.tracer.events
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    # every graph node produced a stage span, tagged with its cluster node
    for node in GRPO_NODES:
        spans = by_name.get(f"stage.{node}")
        assert spans, f"no stage span for {node}"
        assert all(e["cat"] == "graph" for e in spans)
        assert all("cluster_node" in e["args"] for e in spans)
    # the bare (node, idxs) trace tuples are KEPT for bit-identity tests,
    # and every tuple has a span whose idxs match exactly
    assert stats.trace and all(isinstance(t, tuple) for t in stats.trace)
    span_idxs = {(e["args"]["node"], tuple(e["args"]["idxs"]))
                 for e in evs if e.get("cat") == "graph"}
    for name, idxs in stats.trace:
        assert (name, tuple(int(i) for i in idxs)) in span_idxs
    # layout edges + iteration envelope + dock/serve telemetry all landed
    assert "reshard.to_generation" in by_name
    assert "reshard.to_update" in by_name
    assert by_name["iteration"][0]["args"]["iteration"] == 0
    assert "dock.bytes" in by_name and "serve.step" in by_name
    assert by_name["dock.bytes"][-1]["args"]["intranode"] > 0

    # export honors rl.trace_path and the report CLI digests the file
    path = trainer.export_trace()
    assert path == rl.trace_path and Path(path).exists()
    doc = json.load(open(path))
    ts = [e["ts"] for e in doc["traceEvents"]]
    assert ts == sorted(ts) and len(ts) == len(evs)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "trace_report.py"), path,
         "--expect", ",".join(GRPO_NODES)],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    for node in GRPO_NODES:
        assert node in proc.stdout
    assert "dock.bytes" in proc.stdout

    # --expect flags a node that never ran
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "trace_report.py"), path,
         "--expect", "no_such_node"],
        capture_output=True, text=True)
    assert proc.returncode == 1 and "no_such_node" in proc.stderr


def test_export_trace_requires_a_path():
    cfg = get_smoke_config("yi-6b").replace(dtype="float32", remat=False)
    rl = RLConfig(num_generations=2, max_prompt_len=12, max_response_len=8)
    ds = PromptDataset(pattern_task(), max_prompt_len=rl.max_prompt_len,
                       seed=0)
    trainer = GRPOTrainer(cfg, rl, ds, num_nodes=2, seed=0)
    assert not trainer.tracer.enabled                  # no path => default
    with pytest.raises(ValueError, match="trace path"):
        trainer.export_trace()
