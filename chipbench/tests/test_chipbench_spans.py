"""The readers of the program's spans and counters, on hand-made chunk
records: per-chunk differences of the published totals, the twin read one
batch late, and nothing read (no error) from a program that publishes no
span."""

import numpy as np
import pytest

import _paths  # noqa: F401
from _paths import ROOT
from harness import spans, spec
from harness.sut import Chunk

LIVE = ("ready_wait_ms_mean.live", "decide_ms_mean.live",
        "twin_ms_per_batch.live", "twin_slots_per_task.live",
        "d2h_reads_per_batch.live")
REPLAY = ("loop_host_ms_per_ktask.replay",)

# per chunk, its own cycle's growth of each total: one warm-up chunk, then
# batches of 10, 30 and 20 tasks
STEP = {"fetch_wait": 0.001, "pre_place": 0.0005, "predict": 0.002,
        "place": 0.005, "d2h": 0.0015, "execute": 0.016, "tail": 0.0005,
        "stage": 0.0003, "ready_wait": 0.025, "d2h_reads": 18,
        "twin_slots": 2000, "resident_regrows": 0}
SIZES = (8, 10, 30, 20)


def _chunks(scale=(1.0, 1.0, 2.0, 3.0), stats=True):
    """Chunk ``k``'s cycle grows every total by ``scale[k]`` times STEP;
    its twin entry comes one cycle (the consumer spans' sum) after the
    previous one."""
    out, tot, t = [], dict.fromkeys(STEP, 0), 0.0
    for n, s in zip(SIZES, scale):
        for key, v in STEP.items():
            tot[key] += v * s
        t += s * sum(STEP[k] for k in spans.CONSUMER)
        out.append(Chunk(t, t + 0.016 * s, n, dict(tot) if stats else {}))
    return out


def _ctx(**kw):
    ctx = {"chunks": _chunks(**kw), "window_chunks": [1, 2, 3], "warm": 1,
           "traced": [1, 2, 3], "info": {}}
    return ctx


def _read(name, ctx):
    return spec.reader(ROOT, name)(ctx)


def test_live_readers_take_differences_of_the_totals():
    ctx = _ctx()
    # ready_wait: 25 ms x (1, 2, 3), weighted by 10, 30, 20 tasks
    w = np.array([10, 30, 20])
    s = np.array([1.0, 2.0, 3.0])
    assert _read("ready_wait_ms_mean.live", ctx) == pytest.approx(
        25.0 * (w * s).sum() / w.sum())
    assert _read("decide_ms_mean.live", ctx) == pytest.approx(
        9.0 * (w * s).sum() / w.sum())
    # the twin of batches 1 and 2 is read at 2 and 3; batch 3's is unread
    assert _read("twin_ms_per_batch.live", ctx) == pytest.approx(
        16.0 * (2.0 + 3.0) / 2)
    assert _read("twin_slots_per_task.live", ctx) == pytest.approx(
        2000 * (2.0 + 3.0) / (10 + 30))
    assert _read("d2h_reads_per_batch.live", ctx) == pytest.approx(18 * 2)


def test_replay_reader_is_the_loop_host_time_per_ktask():
    ctx = _ctx()
    host = sum(STEP[k] for k in spans.LOOP_HOST)
    assert _read("loop_host_ms_per_ktask.replay", ctx) == pytest.approx(
        host * 6.0 * 1e3 / (60 / 1e3))
    assert "tiling residual median 0.0" in ctx["notes"][0]


def test_untraced_live_readers_read_the_window():
    ctx = _ctx()
    del ctx["traced"]
    assert _read("d2h_reads_per_batch.live", ctx) == pytest.approx(18 * 2)
    assert _read("twin_ms_per_batch.live", ctx) == pytest.approx(16.0 * 2.5)


def test_a_program_without_spans_reads_nothing():
    ctx = _ctx(stats=False)
    ctx["info"] = {"loop": None, "chunk_of": [(0, 10), (10, 40), (40, 60)]}
    for name in LIVE + REPLAY:
        assert _read(name, ctx) is None
    assert "notes" not in ctx


def test_tiling_residual_names_the_time_no_span_holds():
    ctx = _ctx()
    ch = ctx["chunks"]
    ch[3].t_in += 0.004          # 4 ms between two twin entries unspanned
    spans.note_cycles(ctx, [1, 2, 3])
    note = ctx["notes"][-1]
    assert "spans over 3 cycles" in note and "max " in note
    share = 0.004 / (ch[3].t_in - ch[2].t_in)
    assert f"(max {share * 100:.6f}%" in note


def test_latency_parts_add_up_to_the_decision_latency():
    chunk_of = [(0, 10), (10, 40), (40, 60)]
    due = np.linspace(0.0, 0.05, 60)
    release = np.empty(60)
    for lo, hi in chunk_of:          # a batch goes out 2 ms after its last
        release[lo:hi] = due[hi - 1] + 0.002

    class Loop:
        def due(self):
            return due

    loop = Loop()
    loop.release = release
    ctx = _ctx()
    ctx["info"] = {"loop": loop, "chunk_of": chunk_of}
    # each batch's decisions reach the twin exactly its parts after release
    parts = sum(STEP[k] for k in ("stage", "ready_wait") + spans.DECIDE)
    for k, (lo, _), s in zip((1, 2, 3), chunk_of, (1.0, 2.0, 3.0)):
        ctx["chunks"][k].t_in = float(release[lo]) + parts * s
    spans.note_latency_parts(ctx)
    note = ctx["notes"][-1]
    assert note.startswith("decision latency over 60 tasks")
    assert abs(float(note.rsplit("mean residual ", 1)[1])) < 1e-9
