"""The per-layer metrics read from the program's own spans: a reduced
run of each cell on the CPU gives each a positive value, and the
reduction counts what it should."""

import pytest

from bench import program_spans

from .conftest import small_ckpt_cell, small_transfer_cell

READERS = {
    "ckpt": (small_ckpt_cell, ["save.put_s", "save.verify_read_s",
                               "save.commit_s", "restore.place_s"]),
    "transfer": (small_transfer_cell, ["transfer.queue_s",
                                       "transfer.marker_s"]),
}


@pytest.mark.parametrize("which", sorted(READERS))
def test_each_reader_reads_a_traced_run(cpu_run, which):
    make, names = READERS[which]
    cell = make()
    assert set(names) <= {m["name"] for m in cell.per_layer}
    line = cpu_run(cell, trace=True)
    assert line["correct"] is True, line["checks"]
    for name in names:
        assert line["metrics"][name]["value"] > 0, name


def _spans(monkeypatch, spans, dropped=0, dropped_until=float("-inf")):
    from repro import obs
    from repro.obs import HostSpan, HostSpans

    record = [HostSpan(n, "other", a, b, th) for n, a, b, th in spans]
    monkeypatch.setattr(obs, "host_spans", lambda name=None: HostSpans(
        [s for s in record if name is None or s.name == name], dropped,
        dropped_until))


def test_seconds_counts_nested_spans_once_and_threads_apart(monkeypatch):
    _spans(monkeypatch, [("x", 1.0, 3.0, 1), ("x", 1.5, 2.0, 1),
                         ("x", 2.0, 4.0, 2), ("y", 0.0, 9.0, 1)])
    # thread 1: [1, 3]; thread 2: [2, 4]; clipped to [0, 2.5] + [3.5, 9]
    assert program_spans.seconds("x", [(0.0, 2.5), (3.5, 9.0)]) \
        == pytest.approx(1.5 + 0.5 + 0.5)
    # overlapping intervals are one window, not two
    assert program_spans.seconds("x", [(0.0, 2.0), (1.0, 2.0)]) \
        == pytest.approx(1.0)


def test_seconds_gives_nothing_it_cannot_stand_behind(monkeypatch):
    _spans(monkeypatch, [("x", 1.0, 2.0, 1)])
    assert program_spans.seconds("x", []) is None
    assert program_spans.seconds("x", [(5.0, 6.0)]) is None
    assert program_spans.seconds("z", [(0.0, 9.0)]) is None
    # a dropped span that ended inside the window: the window is not whole
    _spans(monkeypatch, [("x", 1.0, 2.0, 1)], dropped=1, dropped_until=0.5)
    assert program_spans.seconds("x", [(0.0, 9.0)]) is None


def test_seconds_reads_past_drops_that_ended_before_the_window(monkeypatch):
    _spans(monkeypatch, [("x", 1.0, 2.0, 1), ("x", 3.0, 5.0, 1)],
           dropped=7, dropped_until=0.5)
    assert program_spans.seconds("x", [(0.5, 4.0)]) == pytest.approx(2.0)
    assert program_spans.seconds("x", [(0.4, 4.0)]) is None
