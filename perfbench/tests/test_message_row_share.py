"""The message-row reader on counters filled by hand: message rows over
staged rows, and None where the program has no message-row counter."""

from types import SimpleNamespace as NS

import pytest

from lighthouse_tpu.common import metrics
from lighthouse_tpu.common.metrics import CounterVec
from perfbench.harness import load_reader


@pytest.fixture
def counters(monkeypatch):
    staged = CounterVec("mr_staged_total", "", ("row",))
    msg = CounterVec("mr_message_total", "", ("row",))
    monkeypatch.setattr(metrics, "BLS_STAGED_ROWS_TOTAL", staged)
    monkeypatch.setattr(metrics, "BLS_MESSAGE_ROWS_TOTAL", msg, raising=False)
    return staged, msg


def test_message_rows_over_staged_rows(counters):
    staged, msg = counters
    read = load_reader("message_row_share.gossip").read
    assert read(NS()) is None  # nothing staged
    staged.labels(row="set").inc(3 * 128 - 2)
    staged.labels(row="pad").inc(2)
    msg.labels(row="distinct").inc(55 + 57 + 74)  # two grouped batches, one full-width
    msg.labels(row="pad").inc(9 + 7 + 54)
    assert read(NS()) == pytest.approx((64 + 64 + 128) / 384)


def test_a_program_without_the_counter_reads_none(counters, monkeypatch):
    staged, _ = counters
    staged.labels(row="set").inc(128)
    monkeypatch.delattr(metrics, "BLS_MESSAGE_ROWS_TOTAL")
    assert load_reader("message_row_share.gossip").read(NS()) is None
