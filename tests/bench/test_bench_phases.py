"""Device idle under the program's serve/* annotations (bench/phases.py), on a
synthetic profile with a known answer: two 100 ms ticks, each with one step
on the device from 7 to 40 ms and one argmax from 70 to 80 ms into the tick,
a compile inside the second tick's launch, and 10 ms with no tick after."""

import pytest

from bench import phases, xtrace

MS = 1_000_000  # ns

# (name, start, end) in ms from the tick's start
TICK = [
    ("tick", 0, 100), ("admit", 1, 2), ("plan", 2, 3), ("tables", 3, 4),
    ("device_step", 5, 60), ("step_inputs", 5, 6), ("step_launch", 6, 8),
    ("step_wait", 8, 40), ("logits_fetch", 40, 50), ("logits_widen", 50, 58),
    ("commit", 61, 95), ("logits_check", 61, 65), ("sample", 65, 85), ("emit", 85, 94),
]
DEVICE = [("jit_step(1)", 7, 40), ("jit__argmax(2)", 70, 80)]
COMPILE = (106.2, 106.8)
WINDOW = (0, 210)


def _plane(pid, name, lines):
    """One XPlane in text form; ``lines`` maps a line name to (event name,
    start ms, end ms) events."""
    names = sorted({n for evs in lines.values() for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{\n  id: {pid}\n  name: "{name}"']
    for k, (ln, evs) in enumerate(lines.items()):
        out.append(f'  lines {{\n    id: {k + 1}\n    name: "{ln}"\n    timestamp_ns: 0')
        out += [f"    events {{ metadata_id: {ids[n]} offset_ps: {round(s * 1e9)} "
                f"duration_ps: {round((e - s) * 1e9)} }}" for n, s, e in evs]
        out.append("  }")
    out += [f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in ids.items()]
    out.append("}")
    return "\n".join(out)


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    mods = [(n, t + s, t + e) for t in (0, 100) for n, s, e in DEVICE]
    host = [("serve/" + n, t + s, t + e) for t in (0, 100) for n, s, e in TICK]
    host.append(("bench/tick", 0, 100))
    text = (_plane(1, "/device:TPU:0", {"XLA Modules": mods, "XLA Ops": []}) + "\n"
            + _plane(2, "/host:CPU", {"python": host}))
    return ProfileData.from_text_proto(text)


def test_annotations_nest_under_their_tick(profile):
    anns = phases.annotations(profile)
    assert len(anns) == 2 * len(TICK)               # bench/tick left out
    assert anns[0] == (0, 100 * MS, "tick") and anns[1][2] == "admit"
    labels = [label for *_, label in phases.labelled(anns[: len(TICK)])]
    assert labels[:5] == ["tick, outside its phases", "tick admit", "tick plan",
                          "tick tables", "tick device_step"]
    assert labels[9] == "tick device_step/logits_widen"
    assert labels[-1] == "tick commit/emit"


def test_innermost_keeps_the_parents_remainder():
    pieces = phases.innermost([(0, 10, "a"), (2, 4, "b"), (2, 3, "c"), (6, 12, "d")])
    assert pieces == [(0, 2, "a"), (2, 3, "c"), (3, 4, "b"), (4, 6, "a"), (6, 12, "d")]
    assert phases.innermost([]) == []


def test_device_step_idle_per_tick(profile):
    anns = phases.annotations(profile)
    modules = xtrace.from_profile(profile).modules
    # device_step 5-60 ms with the step busy 7-40: 2 + 20 ms idle each tick
    assert phases.device_step_idle_ms(anns, modules) == pytest.approx(22.0)
    assert phases.device_step_idle_ms([], modules) is None


def test_idle_by_innermost_span(profile):
    anns = phases.annotations(profile)
    modules = xtrace.from_profile(profile).modules
    compile_ = [(COMPILE[0] * MS, COMPILE[1] * MS, "compile")]
    got = dict(phases.idle_by_span(phases.labelled(anns) + compile_, modules,
                                   WINDOW[0] * MS, WINDOW[1] * MS))
    want = {  # ms over both ticks
        "tick, outside its phases": 16, "tick admit": 2, "tick plan": 2, "tick tables": 2,
        "tick device_step/step_inputs": 2, "tick device_step/step_launch": 1.4,
        "compile": 0.6, "tick device_step/logits_fetch": 20,
        "tick device_step/logits_widen": 16, "tick device_step": 4,
        "tick commit/logits_check": 8, "tick commit/sample": 20, "tick commit/emit": 18,
        "tick commit": 2, "between ticks": 10,
    }
    assert set(got) == set(want)
    for k, v in want.items():
        assert got[k] == pytest.approx(v / 1e3), k
    busy = sum(e - s for _, s, e in DEVICE) * 2
    assert sum(got.values()) == pytest.approx((WINDOW[1] - busy) / 1e3)


def _res(shift_s=3.0):
    """The tracer's record of the same two ticks (µs from the tracer's start,
    which is ``shift_s`` on the host clock)."""
    spans = [{"ph": "X", "name": n, "pid": 1, "tid": 0, "ts": (t + s) * 1e3,
              "dur": (e - s) * 1e3} for t in (0, 100) for n, s, e in TICK]
    spans.append({"ph": "X", "name": "decode", "pid": 2, "tid": 5, "ts": 5e3, "dur": 55e3})
    return {"spans": spans, "tracer_offset_s": shift_s, "t_open": shift_s,
            "t_end": shift_s + 0.2}


def test_host_ms_per_tick_and_logits_host_ms():
    per = phases.host_ms_per_tick(_res())
    assert per["step_wait"] == pytest.approx(32.0) and per["device_step"] == pytest.approx(55.0)
    assert "tick" not in per and "decode" not in per
    # fetch 10 + widen 8 + check 4 + sample 20
    assert phases.logits_host_ms(_res()) == pytest.approx(42.0)
    # a window that holds only the second tick counts it alone
    late = dict(_res(), t_open=3.05)
    assert phases.logits_host_ms(late) == pytest.approx(42.0)
    assert phases.host_ms_per_tick(dict(_res(), t_open=4.0)) == {}
    # a record without the sub-spans (a program that lacks them) reads nothing
    bare = dict(_res(), spans=[e for e in _res()["spans"] if e["name"] in ("tick", "commit")])
    assert phases.logits_host_ms(bare) is None
