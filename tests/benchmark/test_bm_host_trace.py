"""benchmark/lib/host_trace.py and the readers of the program's own
spans and counters: the attribution of device idle time to scheduler
phases on synthetic intervals, every new reader on synthetic ``run`` /
``trace`` dicts (None when its input is absent — a parent commit has
neither the spans nor the counters), and the dump on one real short CPU
capture."""

import json
import os
import threading
import time

import pytest

from benchmark.lib import host_trace as HT
from benchmark.lib import layer_metrics, roofline

from . import helpers as H

MS = 1_000_000
MDIR = os.path.join(H.ROOT, "benchmark", "layer_metrics")
KERNEL = ("%ragged_paged_attention.13 = f32[16,8,16,128]{3,2,1,0} "
          "custom-call(s32[16]{0} %broadcast.1)")

# one scheduler iteration as the engine nests it, times in ms:
# dispatch 10..40 > enqueue:mixed 20..38 > load:mixed 22..36;
# harvest 50..70 > emit 55..65; wait 70..100
SPANS = [
    ["sched:dispatch", 10 * MS, 30 * MS],
    ["sched:enqueue:mixed", 20 * MS, 18 * MS],
    ["load:mixed", 22 * MS, 14 * MS],
    ["sched:harvest", 50 * MS, 20 * MS],
    ["sched:emit", 55 * MS, 10 * MS],
    ["sched:wait", 70 * MS, 30 * MS],
]


def test_flatten_gives_every_instant_to_its_innermost_span():
    segs = HT.flatten(SPANS)
    assert [(s // MS, e // MS, n, r) for s, e, n, r in segs] == [
        (10, 20, "sched:dispatch", "sched:dispatch"),
        (20, 22, "sched:enqueue:mixed", "sched:dispatch"),
        (22, 36, "load:mixed", "sched:dispatch"),
        (36, 38, "sched:enqueue:mixed", "sched:dispatch"),
        (38, 40, "sched:dispatch", "sched:dispatch"),
        (50, 55, "sched:harvest", "sched:harvest"),
        (55, 65, "sched:emit", "sched:harvest"),
        (65, 70, "sched:harvest", "sched:harvest"),
        (70, 100, "sched:wait", "sched:wait"),
    ]
    # a child that runs past its parent's end (clock jitter) never
    # yields a negative or overlapping segment
    segs = HT.flatten([["sched:admit", 0, 100], ["sched:enqueue:kvcopy",
                                                 90, 20]])
    assert segs == [[0, 90, "sched:admit", "sched:admit"],
                    [90, 110, "sched:enqueue:kvcopy", "sched:admit"]]


@pytest.mark.parametrize("idle,by,want", [
    # nesting: an idle interval inside the load goes to the load
    ([(24 * MS, 30 * MS)], "innermost", {"load:mixed": 6 * MS}),
    ([(24 * MS, 30 * MS)], "root", {"sched:dispatch": 6 * MS}),
    # partial overlap: idle 5..15 is half before any span
    ([(5 * MS, 15 * MS)], "innermost",
     {"sched:dispatch": 5 * MS, HT.UNNAMED: 5 * MS}),
    # an uncovered gap between two iterations' spans
    ([(40 * MS, 50 * MS)], "innermost", {HT.UNNAMED: 10 * MS}),
    # one long idle interval across everything
    ([(0, 110 * MS)], "root",
     {"sched:dispatch": 30 * MS, "sched:harvest": 20 * MS,
      "sched:wait": 30 * MS, HT.UNNAMED: 30 * MS}),
    # several intervals, unsorted
    ([(60 * MS, 62 * MS), (12 * MS, 13 * MS)], "innermost",
     {"sched:emit": 2 * MS, "sched:dispatch": 1 * MS}),
])
def test_attribute(idle, by, want):
    assert HT.attribute(idle, SPANS, by) == want


def test_attribute_without_spans_names_nothing():
    assert HT.attribute([(0, 10)], []) == {HT.UNNAMED: 10}
    assert HT.attribute([], SPANS) == {}


def test_phase_of_and_the_scheduler_line():
    assert HT.phase_of("sched:enqueue:mixed") == "enqueue"
    assert HT.phase_of("sched:admit") == "admit"
    assert HT.phase_of("load:decodek") == "load"
    host = {"lines": [
        {"plane": "/host:CPU", "line": "python",
         "events": [["load:embed", 5, 1]]},
        {"plane": "/host:CPU", "line": "llm-engine", "events": SPANS[::-1]},
    ]}
    spans = HT.scheduler_spans(host)
    assert [e[0] for e in spans] == [e[0] for e in SPANS]
    assert HT.scheduler_spans(None) == []
    assert HT.scheduler_spans({"lines": []}) == []


# ---------------------------------------------------------- the readers


def _device(busy):
    """A device plane whose ops are the ``busy`` [start, end) ms."""
    mods = [[f"jit_dispatch_decodek({i})", s * MS, (e - s) * MS]
            for i, (s, e) in enumerate(busy)]
    ops = [[KERNEL, s * MS, (e - s) * MS] for s, e in busy]
    return {"other_planes": ["/host:CPU"], "planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]}]}


def _run_with_host(tmp_path, lines, **more):
    """A run whose capture directory already holds the host dump."""
    pdir = tmp_path / "state" / "profiles" / "stamp"
    pdir.mkdir(parents=True)
    (pdir / "host_trace.json").write_text(json.dumps({"lines": lines}))
    return dict({"profile": {"path": str(pdir)}}, **more)


def _ev(name, trace, run):
    return layer_metrics.evaluate(MDIR, name, trace, run)


def test_idle_readers_on_a_synthetic_capture(tmp_path):
    # the device is busy 0..10, 40..50, 70..72, 100..110 ms: idle 10..40
    # (all under dispatch and its children), 50..70 (harvest), 72..100
    # (wait), so every idle instant has a name
    trace = _device([(0, 10), (40, 50), (70, 72), (100, 110)])
    run = _run_with_host(tmp_path, [
        {"plane": "/host:CPU", "line": "llm-engine", "events": SPANS}])
    assert _ev("idle_named_share", trace, run) == pytest.approx(100.0)
    assert _ev("idle_in_enqueue_share", trace, run) == pytest.approx(
        100.0 * 30 / 110)
    assert _ev("idle_in_harvest_share", trace, run) == pytest.approx(
        100.0 * 20 / 110)
    # busy 0..5 and 45..110: idle 5..45, of which 5..10 has no name
    trace = _device([(0, 5), (45, 110)])
    assert _ev("idle_named_share", trace, run) == pytest.approx(
        100.0 * 30 / 40)
    # a device that never idled: nothing is unnamed
    assert _ev("idle_named_share", _device([(0, 110)]), run) == 100.0
    assert _ev("idle_in_enqueue_share", _device([(0, 110)]), run) == 0.0


@pytest.mark.parametrize("name", ["idle_named_share", "idle_in_enqueue_share",
                                  "idle_in_harvest_share"])
def test_idle_readers_give_nothing_without_their_input(tmp_path, name):
    trace = _device([(0, 10), (40, 50)])
    assert _ev(name, None, {"profile": None}) is None
    assert _ev(name, trace, {"profile": None}) is None
    # a capture of a program without the spans (the parent commit)
    bare = _run_with_host(tmp_path, [])
    assert _ev(name, trace, bare) is None


def _scrape(**families):
    return {fam: [(dict(labels), v) for labels, v in rows]
            for fam, rows in families.items()}


def _phases(seconds):
    return [((("model", "m"), ("phase", ph)), v)
            for ph, v in seconds.items()]


def test_counter_readers_on_synthetic_scrapes():
    before = _scrape(
        engine_sched_phase_seconds_total=_phases(
            {"dispatch": 1.0, "enqueue": 2.0, "wait": 50.0, "emit": 0.5}),
        engine_mixed_dispatch_total=[
            ((("composition", "mixed"),), 10.0),
            ((("composition", "decode_only"),), 90.0)],
        engine_program_loads_total=[
            ((("kind", "mixed"), ("source", "cache")), 3.0)],
        engine_program_load_seconds_sum=[((("kind", "mixed"),), 4.5)],
        engine_dispatch_tokens_total=[
            ((("kind", "mixed"), ("part", "real")), 1000.0),
            ((("kind", "mixed"), ("part", "padded")), 8192.0),
            ((("kind", "decodek"), ("part", "real")), 7.0)])
    after = _scrape(
        engine_sched_phase_seconds_total=_phases(
            {"dispatch": 1.3, "enqueue": 2.5, "wait": 90.0, "emit": 0.7}),
        engine_mixed_dispatch_total=[
            ((("composition", "mixed"),), 20.0),
            ((("composition", "decode_only"),), 180.0)],
        engine_program_loads_total=[
            ((("kind", "mixed"), ("source", "cache")), 4.0)],
        engine_program_load_seconds_sum=[((("kind", "mixed"),), 6.1)],
        engine_dispatch_tokens_total=[
            ((("kind", "mixed"), ("part", "real")), 2048.0),
            ((("kind", "mixed"), ("part", "padded")), 12288.0),
            # admissions that rode a prefill_final count too: a window
            # without one mixed program still has a fill to report
            ((("kind", "prefill_final"), ("part", "real")), 1000.0),
            ((("kind", "prefill_final"), ("part", "padded")), 4096.0),
            ((("kind", "decodek"), ("part", "real")), 9999.0)])
    run = {"metrics_before": before, "metrics_after": after}
    ev = lambda n: _ev(n, None, run)  # noqa: E731
    # 0.3 + 0.5 + 0.2 s of work (wait left out) over 100 dispatches
    assert ev("sched_host_ms_per_dispatch") == pytest.approx(10.0)
    assert ev("program_loads_in_window") == 1.0
    assert ev("program_load_stall_s") == pytest.approx(1.6)
    assert ev("mixed_fill_share") == pytest.approx(100 * 2048 / 8192)


@pytest.mark.parametrize("name", [
    "sched_host_ms_per_dispatch", "program_loads_in_window",
    "program_load_stall_s", "mixed_fill_share",
    "attn_kernel_roofline_counted", "decode_rows_mean"])
def test_counter_readers_give_nothing_on_a_program_without_them(name):
    """A ``delta`` whose family the scrape lacks, and a ``ratio`` whose
    numerator's family it lacks while the denominator counts on
    (``decode_rows_mean``: it read 0 rows), read nothing, not 0."""
    old = _scrape(engine_mixed_dispatch_total=[
        ((("composition", "mixed"),), 10.0)])
    new = _scrape(engine_mixed_dispatch_total=[
        ((("composition", "mixed"),), 20.0)])
    run = {"metrics_before": old, "metrics_after": new,
           "config": H.TINY, "peaks": {"hbm_bytes_per_s": 1e9},
           "profile": {"before": old, "after": new, "path": None}}
    assert _ev(name, _device([(0, 10)]), run) is None
    assert _ev(name, None, {}) is None


def test_attn_kernel_roofline_counted():
    # two kernel calls of 4 ms inside decode programs; between the two
    # scrapes 10 decode steps read 40960 context tokens: 4096 a step
    trace = _device([(0, 4), (10, 14)])
    ctx = "engine_attn_context_tokens_total"
    prof = {"before": _scrape(**{
        ctx: [((("kind", "decodek"),), 1000.0),
              ((("kind", "mixed"),), 5.0)],
        "engine_decode_steps_total": [((), 100.0)]}),
        "after": _scrape(**{
            ctx: [((("kind", "decodek"),), 41960.0),
                  ((("kind", "mixed"),), 999999.0)],
            "engine_decode_steps_total": [((), 110.0)]})}
    cfg = dict(H.TINY)
    run = {"profile": prof, "config": cfg,
           "peaks": {"hbm_bytes_per_s": 1e9}}
    nbytes = 4096 * roofline.kv_bytes_per_token(cfg, layers=1)
    want = 100.0 * (2 * nbytes / 1e9) / 0.008
    assert _ev("attn_kernel_roofline_counted", trace, run) == \
        pytest.approx(want)
    assert 0 < want < 100
    # the window's closing scrape, 0.5 s after the capture's end, is
    # the tighter bracket: the capture's own second scrape is taken
    # seconds of serving later (here it saw other contexts)
    late = _scrape(**{ctx: [((("kind", "decodek"),), 99960.0)],
                      "engine_decode_steps_total": [((), 120.0)]})
    tight = dict(run, seconds=51.0, metrics_after=prof["after"],
                 profile=dict(prof, after=late, t_before=47.5,
                              duration=3.0))
    assert _ev("attn_kernel_roofline_counted", trace, tight) == \
        pytest.approx(want)
    # a capture placed elsewhere in the window keeps its own scrapes
    early = dict(tight, profile=dict(tight["profile"], t_before=20.0))
    assert _ev("attn_kernel_roofline_counted", trace, early) == \
        pytest.approx(want * (98960 / 20) / 4096)
    # no decode step between the scrapes: nothing to divide by
    flat = dict(run, profile={"before": prof["after"],
                              "after": prof["after"]})
    assert _ev("attn_kernel_roofline_counted", trace, flat) is None


def test_new_metrics_are_listed_with_the_layers_the_benchmark_names():
    with open(os.path.join(H.ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    by = {m["name"]: m for m in man["per_layer"]}
    layers = {m["layer"] for m in man["per_layer"][:10]}
    for name in ("idle_named_share", "idle_in_enqueue_share",
                 "idle_in_harvest_share", "sched_host_ms_per_dispatch",
                 "program_loads_in_window", "program_load_stall_s",
                 "mixed_fill_share", "attn_kernel_roofline_counted"):
        assert by[name]["layer"] in layers
        # membership, never equality: later cells append their names
        assert "mistral7b_batch_closed" in by[name]["workloads"]
        assert layer_metrics.find(MDIR, name)


# ------------------------------------------------------------- the dump


def test_dump_of_a_real_capture_keeps_the_programs_spans(tmp_path):
    """One short CPU capture with TraceAnnotations under the names the
    scheduler uses, dumped by the child exactly as a run does it, and
    cached beside the run's trace.json."""
    import jax

    run_dir = tmp_path / "run"
    pdir = run_dir / "state" / "profiles" / "stamp"
    pdir.mkdir(parents=True)
    (run_dir / "trace.json").write_text("{}")

    def sched():
        for _ in range(3):
            with jax.profiler.TraceAnnotation("sched:dispatch"):
                with jax.profiler.TraceAnnotation("sched:enqueue:mixed",
                                                  key="('mixed',)"):
                    time.sleep(0.002)
            with jax.profiler.TraceAnnotation("not-ours"):
                time.sleep(0.001)

    jax.profiler.start_trace(str(pdir))
    th = threading.Thread(target=sched)
    th.start()
    th.join()
    jax.profiler.stop_trace()
    host = HT.load({"profile": {"path": str(pdir)}})
    assert (run_dir / "host_trace.json").exists()
    spans = HT.scheduler_spans(host)
    names = [e[0] for e in spans]
    assert names.count("sched:dispatch") == 3
    assert names.count("sched:enqueue:mixed") == 3
    assert "not-ours" not in names
    segs = HT.flatten(spans)
    assert {s[3] for s in segs} == {"sched:dispatch"}
    assert sum(e - s for s, e, n, _r in segs
               if n == "sched:enqueue:mixed") >= 3 * 2 * MS
    # a second load reads the cache (the capture may be gone by then)
    for p in pdir.rglob("*.xplane.pb"):
        p.unlink()
    assert HT.load({"profile": {"path": str(pdir)}}) == host
    # no capture: nothing
    assert HT.load({"profile": {"path": str(tmp_path / "none")}}) is None
    assert HT.load({}) is None
