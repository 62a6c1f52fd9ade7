"""The readers of the scheduler-by-cause counters and spans (PR 43):
``sched_busy_share``, ``admit_host_ms_per_request``,
``idle_in_admit_share``, ``decode_stalls_in_window``,
``device_starved_share``, ``tier_host_ms_per_spill``,
``kv_spill_mb_per_request`` — each on synthetic scrapes or synthetic
intervals, and each reading nothing (never 0) from a program without
its family: the parent commit has the phase counter and the tier's
counters, not the per-span, stall or starved families."""

import json
import os

import pytest

from benchmark.lib import layer_metrics

from . import helpers as H

MS = 1_000_000
MDIR = os.path.join(H.ROOT, "benchmark", "layer_metrics")
NEW = ["sched_busy_share", "admit_host_ms_per_request",
       "idle_in_admit_share", "decode_stalls_in_window",
       "device_starved_share", "tier_host_ms_per_spill",
       "kv_spill_mb_per_request"]
KERNEL = ("%ragged_paged_attention.13 = f32[16,8,16,128]{3,2,1,0} "
          "custom-call(s32[16]{0} %broadcast.1)")


def _ev(name, trace, run):
    return layer_metrics.evaluate(MDIR, name, trace, run)


def _scrape(**families):
    return {fam: [(dict(labels), v) for labels, v in rows]
            for fam, rows in families.items()}


def _by(label, values):
    return [((("model", "m"), (label, k)), v) for k, v in values.items()]


def _scrapes():
    before = _scrape(
        engine_sched_phase_seconds_total=_by("phase", {
            "admit": 10.0, "enqueue": 2.0, "state": 1.0, "wait": 50.0}),
        engine_sched_span_seconds_total=_by("span", {
            "sched:admit": 1.0, "sched:admit:tier": 6.0,
            "sched:admit:prefix": 0.5, "sched:admit:place": 0.5,
            "sched:admit:spill": 1.5, "sched:admit:assign": 0.5,
            "sched:enqueue:mixed": 1.5, "sched:enqueue:decodek": 0.5,
            "sched:state": 1.0, "sched:wait": 50.0}),
        engine_requests_total=[((("reason", "length"),), 100.0),
                               ((("reason", "stop"),), 4.0)],
        engine_sched_stalls_total=_by("cause", {
            "admit:tier": 1.0, "load": 3.0, "wait": 0.0}),
        engine_sched_stall_seconds_total=_by("cause", {
            "admit:tier": 0.5, "load": 9.0, "wait": 0.0}),
        engine_device_starved_seconds_total=[((("model", "m"),), 0.25)],
        engine_kv_tier_moves_total=[
            ((("direction", "spill"), ("outcome", "ok")), 90.0),
            ((("direction", "spill"), ("outcome", "dedup")), 7.0),
            ((("direction", "save"), ("outcome", "aborted")), 80.0)],
        engine_kv_tier_bytes_moved_total=[
            ((("direction", "spill"),), 5.0e9),
            ((("direction", "fetch"),), 1.0e9)])
    after = _scrape(
        engine_sched_phase_seconds_total=_by("phase", {
            "admit": 14.0, "enqueue": 3.0, "state": 2.0, "wait": 84.0}),
        engine_sched_span_seconds_total=_by("span", {
            "sched:admit": 1.2, "sched:admit:tier": 8.4,
            "sched:admit:prefix": 0.7, "sched:admit:place": 0.6,
            "sched:admit:spill": 2.3, "sched:admit:assign": 0.8,
            "sched:enqueue:mixed": 2.2, "sched:enqueue:decodek": 0.8,
            "sched:state": 2.0, "sched:wait": 84.0}),
        engine_requests_total=[((("reason", "length"),), 138.0),
                               ((("reason", "stop"),), 6.0)],
        engine_sched_stalls_total=_by("cause", {
            "admit:tier": 2.0, "load": 3.0, "wait": 1.0}),
        engine_sched_stall_seconds_total=_by("cause", {
            "admit:tier": 0.9, "load": 9.0, "wait": 0.3}),
        engine_device_starved_seconds_total=[((("model", "m"),), 0.76)],
        engine_kv_tier_moves_total=[
            ((("direction", "spill"), ("outcome", "ok")), 130.0),
            ((("direction", "spill"), ("outcome", "dedup")), 99.0),
            ((("direction", "save"), ("outcome", "aborted")), 120.0)],
        engine_kv_tier_bytes_moved_total=[
            ((("direction", "spill"),), 7.4e9),
            ((("direction", "fetch"),), 9.0e9)])
    return before, after


def test_window_long_readers_on_synthetic_scrapes(capsys):
    before, after = _scrapes()
    run = {"metrics_before": before, "metrics_after": after,
           "seconds": 51.0}
    ev = lambda n: _ev(n, None, run)  # noqa: E731
    # 4 + 1 + 1 s of work (state counted) of 40 s on the clock
    assert ev("sched_busy_share") == pytest.approx(100.0 * 6 / 40)
    # 4 s of admit over 40 completed requests, whatever their reason
    assert ev("admit_host_ms_per_request") == pytest.approx(100.0)
    out = capsys.readouterr().out
    assert "'sched:admit:tier': 60.0" in out and "'sched:admit': 5.0" in out
    assert "(sum 100.0000)" in out  # the parts and the remainder tile it
    assert ev("decode_stalls_in_window") == 2.0
    out = capsys.readouterr().out
    assert "'admit:tier': (1.0, 0.4)" in out and "'wait': (1.0, 0.3)" in out
    assert "load" not in out  # a cause that did not move is not listed
    assert ev("device_starved_share") == pytest.approx(1.0)
    # (2.4 + 0.8) s of tick and capture over 40 spills that landed
    assert ev("tier_host_ms_per_spill") == pytest.approx(80.0)
    # 2.4 GB spilled (fetches are not spills) over 40 requests
    assert ev("kv_spill_mb_per_request") == pytest.approx(60.0)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_give_nothing_on_a_program_without_their_family(name):
    """A scrape with none of the families these read (requests alone):
    nothing, never 0. The capture reader: no capture, or a capture of a
    program that wrote no span."""
    old = _scrape(engine_requests_total=[((("reason", "length"),), 10.0)])
    new = _scrape(engine_requests_total=[((("reason", "length"),), 20.0)])
    run = {"metrics_before": old, "metrics_after": new, "seconds": 51.0,
           "profile": None}
    assert _ev(name, None, run) is None
    assert _ev(name, _device([(0, 10)]), run) is None
    assert _ev(name, None, {}) is None


def test_readers_of_families_the_parent_has_read_the_parent():
    """The phase counter and the tier's counters are older than the
    per-span family: on a parent's scrape the busy share, the admission
    time (its total, no split) and the spill megabytes are numbers,
    the per-spill host time (per-span seconds) is nothing."""
    before, after = _scrapes()
    for s in (before, after):
        for fam in ("engine_sched_span_seconds_total",
                    "engine_sched_stalls_total",
                    "engine_sched_stall_seconds_total",
                    "engine_device_starved_seconds_total"):
            del s[fam]
    run = {"metrics_before": before, "metrics_after": after,
           "seconds": 51.0}
    assert _ev("sched_busy_share", None, run) == pytest.approx(15.0)
    assert _ev("admit_host_ms_per_request", None, run) == pytest.approx(100.0)
    assert _ev("kv_spill_mb_per_request", None, run) == pytest.approx(60.0)
    for name in ("tier_host_ms_per_spill", "decode_stalls_in_window",
                 "device_starved_share"):
        assert _ev(name, None, run) is None


def test_ratio_readers_need_something_to_divide_by():
    before, after = _scrapes()
    run = {"metrics_before": after, "metrics_after": after, "seconds": 51.0}
    for name in ("sched_busy_share", "admit_host_ms_per_request",
                 "tier_host_ms_per_spill", "kv_spill_mb_per_request"):
        assert _ev(name, None, run) is None
    # no stall and no starved second in a window is a 0, not nothing
    assert _ev("decode_stalls_in_window", None, run) == 0.0
    assert _ev("device_starved_share", None, run) == 0.0
    assert _ev("device_starved_share", None, dict(run, seconds=0.0)) is None


# ------------------------------------------------ the capture's reader


def _device(busy):
    """A device plane whose ops are the ``busy`` [start, end) ms."""
    mods = [[f"jit_dispatch_decodek({i})", s * MS, (e - s) * MS]
            for i, (s, e) in enumerate(busy)]
    ops = [[KERNEL, s * MS, (e - s) * MS] for s, e in busy]
    return {"other_planes": ["/host:CPU"], "planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]}]}


def _run_with_host(tmp_path, events):
    pdir = tmp_path / "state" / "profiles" / "stamp"
    pdir.mkdir(parents=True)
    (pdir / "host_trace.json").write_text(json.dumps({"lines": [
        {"plane": "/host:CPU", "line": "llm-engine", "events": events}]}))
    return {"profile": {"path": str(pdir)}}


# two scheduler iterations, times in ms. The first span of the line
# starts at 20: what the capture holds before it has no name.
#   admit 20..60 > admit:tier 22..40, admit:spill 44..56 > load:kv_gather
#   46..50; dispatch 60.4..70 (0.4 ms of step()'s own statements
#   before it); then the loop stands 70..90 with no span; wait 90..120
SPANS = [
    ["sched:admit", 20 * MS, 40 * MS],
    ["sched:admit:tier", 22 * MS, 18 * MS],
    ["sched:admit:spill", 44 * MS, 12 * MS],
    ["load:kv_gather", 46 * MS, 4 * MS],
    ["sched:dispatch", 60 * MS + 400_000, 10 * MS - 400_000],
    ["sched:wait", 90 * MS, 30 * MS],
]


def test_idle_in_admit_share_and_where_the_unnamed_idle_lies(
        tmp_path, capsys):
    from importlib import util

    run = _run_with_host(tmp_path, SPANS)
    # busy 0..10, 58..62, 66..68, 100..105, 130..140: idle 10..58 (10 ms
    # before the first span, 38 under admit), 62..66 (dispatch), 68..100
    # (2 dispatch, 20 between two roots, 10 wait), 105..130 (15 wait,
    # 10 after the last span)
    trace = _device([(0, 10), (58, 62), (66, 68), (100, 105), (130, 140)])
    span = 140
    assert _ev("idle_in_admit_share", trace, run) == pytest.approx(
        100.0 * 38 / span)
    out = capsys.readouterr().out
    # under admit, by innermost span: tier 18, the load 4, spill 8 - 4
    # + ... the bare admit span keeps what no part covers
    assert f"'sched:admit:tier': {18 * MS}" in out
    assert f"'load:kv_gather': {4 * MS}" in out
    assert f"'sched:admit:spill': {8 * MS}" in out
    assert f"'sched:admit': {8 * MS}" in out
    assert f"'before_first': {10 * MS}" in out
    assert f"'between_roots_1ms_or_more': {20 * MS}" in out
    assert f"'after_last': {10 * MS}" in out
    assert f"unnamed idle ns {40 * MS}" in out
    # the statements of step() between two spans: idle there is named
    # by its own key, apart from the loop's long stands
    trace = _device([(0, 60), (61, 140)])
    assert _ev("idle_in_admit_share", trace, run) == 0.0
    out = capsys.readouterr().out
    assert "'between_roots_under_1ms': 400000" in out
    assert "between_roots_1ms_or_more" not in out
    # the same split from the function itself, on unsorted intervals
    spec = util.spec_from_file_location(
        "idle_in_admit_share", os.path.join(MDIR, "idle_in_admit_share.py"))
    mod = util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.unnamed_split(
        [(105 * MS, 130 * MS), (10 * MS, 58 * MS)], SPANS[::-1]) == {
            "before_first": 10 * MS, "after_last": 10 * MS}
    assert mod.unnamed_split([], SPANS) == {}
    assert mod.unnamed_split([(0, 10)], []) == {}


def test_the_new_entries_name_layers_and_cells():
    with open(os.path.join(H.ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    by = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        m = by[name]
        assert m["moves"] == "tpot_p50_ms" and m["better"] == "lower"
        assert "mistral7b_batch_closed" in m["workloads"]
        assert layer_metrics.find(MDIR, name)
    # the tier's two readers stay off the cell whose model refuses it
    for name in ("tier_host_ms_per_spill", "kv_spill_mb_per_request"):
        assert by[name]["layer"] == "KV tier"
        assert "olmohybrid_docs_closed" not in by[name]["workloads"]
    assert by["device_starved_share"]["layer"] == "device"
    assert by["sched_busy_share"]["layer"] == "scheduler"
