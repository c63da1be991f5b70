"""obs/memwatch.py against stub devices whose ``memory_stats()`` is a
script: what the allocator said at each read, in the order a run reads
(entry, ``init``, the end of each wave, ``finish``). jax-free, as the
module is."""

import pytest

from raft_tpu.obs import NULL_TELEMETRY
from raft_tpu.obs.events import HBM_KEYS, SUMMARY_KEYS, validate_event
from raft_tpu.obs.memwatch import ROW_KEYS, MemWatch

GB = 1 << 30
BUDGET = 16 * GB


class StubDevice:
    """A device that answers ``memory_stats()`` from a script of
    (bytes_in_use, peak_bytes_in_use) pairs, one a read; ``None`` for a
    backend that keeps no such statistics."""

    platform, device_kind = "tpu", "stub"

    def __init__(self, script):
        self.script = list(script) if script is not None else None
        self.reads = 0

    def memory_stats(self):
        if self.script is None:
            return None
        in_use, peak = self.script[min(self.reads, len(self.script) - 1)]
        self.reads += 1
        return {"bytes_limit": BUDGET, "bytes_in_use": in_use,
                "peak_bytes_in_use": peak}


class CpuDevice(StubDevice):
    platform, device_kind = "cpu", "cpu"


class Listening:
    """A telemetry facade that keeps what it is told."""

    active = True

    def __init__(self):
        self.events = []

    def event(self, etype, **fields):
        self.events.append({"event": etype, **fields})


def watched(script, plans, tel=NULL_TELEMETRY, device=StubDevice):
    """A run's worth of reads: entry (``device_budget`` asks the stub
    once before it, which the script's first pair answers too), ``init``,
    one wave a plan, ``finish``. Returns the rows and the run's fields."""
    script = [script[0], *script] if script is not None else None
    mw = MemWatch(tel, [device(script)])
    mw.init()
    rows = [mw.wave(depth, plan) for depth, plan in enumerate(plans, 1)]
    return rows, mw.finish()


# entry, init, waves 1-3, finish
FIRST_RUN = [(0, 0), (6 * GB, 9 * GB), (6 * GB, 9 * GB),
             (6 * GB + 5, 10 * GB), (6 * GB + 2, 10 * GB),
             (6 * GB + 2, 10 * GB)]
PLAN = {"frontier": 6 * GB, "seen": GB // 4, "empty": 0}


def test_a_rise_of_the_peak_is_booked_to_its_interval_and_to_no_other():
    rows, run = watched(FIRST_RUN, [PLAN] * 3)
    assert run["hbm_init_bytes"] == 6 * GB
    assert run["hbm_init_rise"] == 9 * GB  # the peak was set in init
    assert [r["hbm_peak_rise"] for r in rows] == [0, GB, 0]
    assert run["hbm_peak_bytes"] == 10 * GB
    # a warmed process: the allocator's peak is the process's and no
    # interval of this run raised it
    warm = [(GB, 10 * GB)] * 6
    rows, run = watched(warm, [PLAN] * 3)
    assert run["hbm_init_rise"] == 0
    assert [r["hbm_peak_rise"] for r in rows] == [0, 0, 0]
    assert run["hbm_peak_bytes"] == 10 * GB


@pytest.mark.parametrize("script, init_rise, wave_rise, peak", [
    # entry, init, wave 1, finish: all of it init's
    ([(0, 0), (GB, GB), (GB, GB), (GB, GB)], GB, 0, GB),
    # a rise after the last wave's read (the last wave of a run that
    # ends `exhausted`) is in the peak and on no row
    ([(0, 0), (0, 0), (GB, GB), (GB, 2 * GB)], 0, GB, 2 * GB),
])
def test_the_rises_and_the_peak_say_where_it_rose(
        script, init_rise, wave_rise, peak):
    rows, run = watched(script, [PLAN])
    assert run["hbm_init_rise"] == init_rise
    assert [r["hbm_peak_rise"] for r in rows] == [wave_rise]
    assert run["hbm_peak_bytes"] == peak


def test_a_run_that_hands_in_no_device_measures_nothing(monkeypatch):
    """The host engine's: its arrays are not the allocator's."""
    monkeypatch.setenv("RAFT_TPU_HBM_BUDGET", str(4 * GB))
    mw = MemWatch(NULL_TELEMETRY, ())
    mw.init()
    row = mw.wave(1, {"frontier": GB})
    run = mw.finish()
    assert row == {"hbm_bytes": None, "hbm_peak_rise": None, "hbm_frac": 0.25}
    assert run["hbm_budget_bytes"] == 4 * GB and run["hbm_plan_frac"] == 0.25
    assert all(run[k] is None for k in run if "plan" not in k
               and k != "hbm_budget_bytes")


def test_live_is_the_largest_wave_end_read_and_the_fractions_are_as_defined():
    rows, run = watched(FIRST_RUN, [PLAN] * 3)
    assert [r["hbm_bytes"] for r in rows] == [6 * GB, 6 * GB + 5, 6 * GB + 2]
    assert run["hbm_live_bytes"] == 6 * GB + 5  # neither init's nor finish's
    assert [tuple(r) for r in rows] == [ROW_KEYS] * 3
    assert rows[1]["hbm_frac"] == round((6 * GB + 5) / BUDGET, 6)
    plan = 6 * GB + GB // 4
    assert run["hbm_budget_bytes"] == BUDGET
    assert run["hbm_plan_bytes"] == plan
    assert run["hbm_plan_frac"] == plan / BUDGET
    assert run["hbm_peak_frac"] == 10 * GB / BUDGET
    assert run["hbm_live_frac"] == (6 * GB + 5) / BUDGET
    assert run["hbm_plan_gap_frac"] == (10 * GB - plan) / (10 * GB)
    assert tuple(run) == HBM_KEYS
    assert validate_event({
        **dict.fromkeys(SUMMARY_KEYS, 0), "event": "summary",
        "exit_cause": "exhausted", **run}) == []


def test_the_gap_is_negative_where_the_plan_over_counts():
    _, run = watched([(GB, GB)] * 4, [{"frontier": 2 * GB}])
    assert run["hbm_plan_gap_frac"] == -1.0
    # and the plan keeps its largest wave, whatever the allocator says
    _, run = watched([(GB, GB)] * 6, [{"a": 5}, {"a": 9}, {"a": 7}])
    assert run["hbm_plan_bytes"] == 9


def test_a_device_that_reports_nothing_gives_none_and_the_plan_stands(
        monkeypatch):
    monkeypatch.setenv("RAFT_TPU_HBM_BUDGET", str(8 * GB))
    rows, run = watched(None, [PLAN, PLAN], device=CpuDevice)
    plan = 6 * GB + GB // 4
    assert all(r["hbm_bytes"] is None and r["hbm_peak_rise"] is None
               for r in rows)
    assert rows[0]["hbm_frac"] == round(plan / (8 * GB), 6)  # the plan's
    assert run == {
        "hbm_budget_bytes": 8 * GB, "hbm_peak_bytes": None,
        "hbm_live_bytes": None, "hbm_init_bytes": None,
        "hbm_init_rise": None,
        "hbm_plan_bytes": plan, "hbm_plan_frac": plan / (8 * GB),
        "hbm_peak_frac": None, "hbm_live_frac": None,
        "hbm_plan_gap_frac": None,
    }


def test_an_accelerator_without_a_limit_is_an_error_not_a_guess():
    class NoLimit(StubDevice):
        def memory_stats(self):
            return {"bytes_in_use": 1, "peak_bytes_in_use": 1}

    with pytest.raises(RuntimeError, match="bytes_limit"):
        MemWatch(NULL_TELEMETRY, [NoLimit([])])


def test_the_fullest_device_of_a_mesh_is_the_reading():
    a = StubDevice([(0, 0)] * 2 + [(3, 9), (4, 9)])
    b = StubDevice([(0, 0)] * 1 + [(5, 7), (2, 8)])
    mw = MemWatch(NULL_TELEMETRY, [a, b])  # two reads of a, one of b
    mw.init()
    assert mw.init_bytes == 5 and mw.init_rise == 9
    assert mw.wave(1, {})["hbm_bytes"] == 4


def test_a_memwatch_event_goes_out_on_a_new_plan_peak_or_a_rise():
    tel = Listening()
    watched(FIRST_RUN, [PLAN, PLAN, {**PLAN, "seen": GB}], tel=tel)
    # wave 1: the first plan; wave 2: the peak rose; wave 3: a new plan
    assert [e["wave"] for e in tel.events] == [1, 2, 3]
    assert [e["peak_rise"] for e in tel.events] == [0, GB, 0]
    assert [e["plan_peak_bytes"] for e in tel.events] == [
        6 * GB + GB // 4] * 2 + [7 * GB]
    assert tel.events[1]["breakdown"] == {"frontier": 6 * GB,
                                         "seen": GB // 4}
    assert all(validate_event(e) == [] for e in tel.events)
    # nobody listening: the same readings, no event
    quiet = Listening()
    quiet.active = False
    rows, _ = watched(FIRST_RUN, [PLAN] * 3, tel=quiet)
    assert quiet.events == [] and rows[1]["hbm_peak_rise"] == GB
