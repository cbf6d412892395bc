"""The reader of the program's ``late_pulls`` counter, on hand-made chunk
records: the share of the spanned micro-batches whose pull was held, and
nothing read (no error) from a program that does not count it."""

import pytest

import _paths  # noqa: F401
from _paths import ROOT
from harness import spec
from harness.sut import Chunk

NAME = "late_pull_share.live"


def _ctx(late_pulls, traced=None):
    """One chunk per entry of ``late_pulls`` (the published total at its
    twin entry, or None where the program publishes none), 20 ms apart;
    the first is the warm-up."""
    chunks = [Chunk(0.02 * k, 0.02 * k + 0.004, 40,
                    {} if v is None else {"late_pulls": v, "d2h_reads": 0})
              for k, v in enumerate(late_pulls)]
    window = list(range(1, len(chunks)))
    ctx = {"chunks": chunks, "window_chunks": window, "warm": 1,
           "info": {}}
    if traced is not None:
        ctx["traced"] = traced
    return ctx


def _read(ctx):
    return spec.reader(ROOT, NAME)(ctx)


@pytest.mark.parametrize("totals,traced,share", [
    ([0, 1, 2, 3, 4], None, 1.0),           # every window batch held
    ([3, 3, 3, 3, 3], None, 0.0),           # full chunks: none held
    ([0, 1, 1, 2, 3], None, 0.75),          # one batch after a full chunk
    ([0, 1, 1, 2, 3], [3, 4], 1.0),         # the traced span only
], ids=["all", "none", "mixed", "traced"])
def test_share_of_batches_pulled_late(totals, traced, share):
    assert _read(_ctx(totals, traced)) == pytest.approx(share)


def test_a_program_without_the_counter_reads_nothing():
    assert _read(_ctx([None] * 5)) is None


def test_the_metric_reads_the_live_cell_only():
    bench = spec.load(ROOT)
    m = [p for p in bench["per_layer"] if p["name"] == NAME]
    assert len(m) == 1 and m[0]["workloads"] == ["ir19-minlat0.live"]
    assert m[0]["moves"] == "decision_p50_ms"
