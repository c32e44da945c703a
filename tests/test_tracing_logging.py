"""core.logging (spdlog analog): callback sink, pattern, and level
round-trips.
"""
import pytest

from raft_tpu.core import logging as rlog


@pytest.fixture
def logging_state():
    """Detach any callback sink and restore INFO afterwards."""
    yield
    rlog.set_callback(None)
    rlog.set_level(rlog.LEVEL_INFO)


# -- logging ----------------------------------------------------------------


def test_set_level_get_level_round_trip(logging_state):
    for lvl in (
        rlog.LEVEL_OFF,
        rlog.LEVEL_CRITICAL,
        rlog.LEVEL_ERROR,
        rlog.LEVEL_WARN,
        rlog.LEVEL_INFO,
        rlog.LEVEL_DEBUG,
        rlog.LEVEL_TRACE,
    ):
        rlog.set_level(lvl)
        assert rlog.get_level() == lvl
    rlog.set_level(999)  # unknown levels fall back to INFO
    assert rlog.get_level() == rlog.LEVEL_INFO


def test_callback_sink_receives_messages(logging_state):
    got = []
    rlog.set_callback(lambda lvl, msg: got.append((lvl, msg)))
    rlog.set_pattern("%(message)s")
    rlog.set_level(rlog.LEVEL_INFO)
    rlog.info("hello %d", 42)
    rlog.warn("careful")
    rlog.debug("filtered out")  # below INFO
    assert [m for _, m in got] == ["hello 42", "careful"]
    import logging as pylogging

    assert got[0][0] == pylogging.INFO
    assert got[1][0] == pylogging.WARNING


def test_set_pattern_changes_format(logging_state):
    got = []
    rlog.set_callback(lambda lvl, msg: got.append(msg))
    rlog.set_level(rlog.LEVEL_INFO)
    rlog.set_pattern("[%(levelname)s] %(message)s")
    rlog.error("boom")
    assert got == ["[ERROR] boom"]


def test_trace_macro_and_level_gate(logging_state):
    got = []
    rlog.set_callback(lambda lvl, msg: got.append(msg))
    rlog.set_pattern("%(message)s")
    rlog.set_level(rlog.LEVEL_TRACE)
    rlog.trace("deep %s", "detail")
    assert got == ["deep detail"]
    got.clear()
    rlog.set_level(rlog.LEVEL_OFF)
    rlog.critical("silenced")
    assert got == []


def test_callback_removal(logging_state):
    got = []
    rlog.set_callback(lambda lvl, msg: got.append(msg))
    rlog.set_pattern("%(message)s")
    rlog.set_level(rlog.LEVEL_INFO)
    rlog.info("one")
    rlog.set_callback(None)
    assert rlog._cb_handler not in rlog.logger.handlers
    rlog.info("two")  # no sink: dropped by the NullHandler
    assert got == ["one"]
