"""The program's spans in a profiler trace (bench/program_trace.py): kept
by ``load`` from a real trace, reduced to self and device time, naming the
idle gaps, and leaving every number of ``bench.trace_reduce`` as it was."""

import json
from pathlib import Path

import pytest

from bench import program_trace, trace_reduce
from bench.trace_reduce import OPS_LINE, WINDOW

#: the first 200 device ops of a traced window on a TPU v5e, with the host
#: and program spans inside them (``trace_reduce.trim`` plus the program's)
RECORDED = {p.stem: p for p in (Path(__file__).parent / "traces").glob("*.json")}
SEARCH_SPANS = {"advisor.decompose", "estimator.features", "network.pack", "network.launch"}


def _hand_trace():
    return {
        "device": {"/device:TPU:0": {
            OPS_LINE: [["fusion.1", 10, 20], ["fusion.2", 15, 30], ["copy", 50, 60],
                       ["late", 90, 130]],
        }},
        "host": [[WINDOW, 0, 100], ["bench.x", 5, 35], ["bench.x", 45, 70]],
        # pack [45, 69] holds two features and the launch; the copy [50, 60]
        # runs under them; a span on another line is no child of pack
        "program": [["network.pack", 45, 69, "python"],
                    ["estimator.features", 46, 50, "python"],
                    ["estimator.features", 52, 55, "python"],
                    ["network.launch", 56, 68, "python"],
                    ["estimator.features", 47, 49, "worker"]],
    }


def test_program_spans_reduce_to_self_and_device_time():
    r = program_trace.reduce_events(_hand_trace())
    ns = {name: {k: round(v * 1e9) if k != "calls" else v for k, v in d.items()}
          for name, d in r["program"].items()}
    assert ns["network.pack"] == {"calls": 1, "host_s": 24, "self_s": 5, "device_s": 10}
    assert ns["estimator.features"] == {"calls": 3, "host_s": 9, "self_s": 9, "device_s": 3}
    assert ns["network.launch"] == {"calls": 1, "host_s": 12, "self_s": 12, "device_s": 4}
    # no program span holds a gap's middle here: the labels are the old ones
    gaps = [(name, round(s * 1e9)) for name, s in r["idle_gaps"]]
    assert gaps == [("outside benchmark calls", 30), ("outside benchmark calls", 20),
                    ("bench.x", 10)]


def test_idle_gaps_are_named_by_the_innermost_span():
    events = _hand_trace()
    events["device"]["/device:TPU:0"][OPS_LINE] = [["a", 0, 46], ["b", 56, 100]]
    gaps = program_trace.reduce_events(events)["idle_gaps"]
    # [46, 56] has its middle at 51: inside pack, between the two features
    assert [(name, round(s * 1e9)) for name, s in gaps] == [("network.pack", 10)]
    events["device"]["/device:TPU:0"][OPS_LINE] = [["a", 0, 47], ["b", 49, 100]]
    gaps = program_trace.reduce_events(events)["idle_gaps"]
    assert gaps[0][0] == "estimator.features"


@pytest.mark.parametrize("trace", ["hand", *sorted(RECORDED)])
def test_existing_numbers_unchanged_beside_the_program_reduction(trace):
    events = _hand_trace() if trace == "hand" else json.loads(RECORDED[trace].read_text())
    old = trace_reduce.reduce_events({k: events[k] for k in ("device", "host")})
    new = program_trace.reduce_events(events)
    assert set(new) == set(old) | {"program"}
    for key in ("busy_s", "window_s", "devices", "spans", "device_ops"):
        assert new[key] == old[key]
    # every gap keeps its length; only its label may name a program span
    assert sorted(s for _, s in new["idle_gaps"]) == sorted(s for _, s in old["idle_gaps"])
    names = {name for name, *_ in events["program"]}
    assert {name for name, _ in new["idle_gaps"]} <= names | {
        name for name, _ in old["idle_gaps"]}


def test_recorded_search_step_waits_on_the_launch():
    r = program_trace.reduce_events(json.loads(RECORDED["olmoe.mesh_search"].read_text()))
    assert set(r["program"]) == SEARCH_SPANS
    assert r["program"]["estimator.features"]["calls"] == 4  # one a layer-type group
    # the longest gap, before the step's first device op, ends in the launch
    assert r["idle_gaps"][0][0] == "network.launch"


def test_load_keeps_the_program_spans_of_a_real_trace(tiny_hub, tmp_path):
    import jax

    import repro.obs as obs
    from bench import estimators
    from bench.common import Cell
    from repro.core.advisor import autotune, default_candidates
    from repro.models.config import InputShape, ModelConfig

    path, platform, _ = tiny_hub
    oracle = estimators.load_oracle(path, platform)
    cell = Cell("olmoe.mesh_search")
    model = ModelConfig(**cell.config["model"])
    shape = InputShape(name="train_4k", **cell.traffic["shapes"]["train_4k"])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.tracing(obs.Tracer(None, profiler=True)):
            with jax.profiler.TraceAnnotation(WINDOW):
                with jax.profiler.TraceAnnotation("bench.search"):
                    autotune(oracle, model, shape, default_candidates(64))
    finally:
        jax.profiler.stop_trace()
    (xplane,) = tmp_path.rglob("*.xplane.pb")
    events = program_trace.load(str(xplane))
    base = trace_reduce.load(str(xplane))
    assert events["host"] == base["host"] and events["device"] == base["device"]
    # only the program's own spans match the prefixes, each on its thread line
    assert {name for name, *_ in events["program"]} == SEARCH_SPANS
    assert all(isinstance(line, str) and line for *_, line in events["program"])
    (search,) = [h for h in events["host"] if h[0] == "bench.search"]
    assert all(search[1] <= s <= e <= search[2] for _, s, e, _ in events["program"])
    assert program_trace._child_ns(events["program"]) != [0.0] * len(events["program"])

