"""The measured window's one loop: a family of several clients finishes
overlapping jobs of different sizes in one call, and each is a job of the
window with its own times and commands; a single-client family goes
through the same loop, one job a call."""
import time
import weakref

import numpy as np
import pytest

import harness
from bench_cases import tiny_spec

CELLS = ("shift_n512", "shift_n1")


def _span(name):
    import contextlib
    return contextlib.nullcontext()


class _State:
    """A job's device state, as the check fetches it."""

    def __init__(self):
        for k in harness.STATE:
            setattr(self, k, np.zeros(1))


class FakeCell(harness.JobCell):
    """Three clients; client ``c``'s jobs take ``c + 1`` calls and
    ``100 * (c + 1)`` commands. Each call is one step of every client, and
    ends the jobs whose steps ran out: they overlap and differ in size."""

    def __init__(self):
        self.device0 = None
        self.calls = 0
        self.done: list = []            # every Done the window's calls gave
        self.checked: list = []         # the inputs the check compared
        self.jobs = {c: None for c in range(3)}
        self.next = [0, 0, 0]
        self.states: list = []          # weak references to every output
        self.most_alive = 0             # outputs alive at the start of a call

    def make_device(self):
        pass

    def warm_up(self, span):
        return 0.01

    def inputs(self, i):
        return i

    def serve(self, inp, span):
        self.most_alive = max(self.most_alive,
                              sum(r() is not None for r in self.states))
        now = time.perf_counter()
        for c, job in self.jobs.items():
            if job is None:
                self.jobs[c] = [(c, self.next[c]), now, c + 1]
                self.next[c] += 1
        time.sleep(0.002)
        self.calls += 1
        t_done = time.perf_counter()
        out = []
        for c, job in self.jobs.items():
            job[2] -= 1
            if job[2] == 0:
                state = _State()
                self.states.append(weakref.ref(state))
                out.append(harness.Done(job[1], t_done, 100 * (c + 1),
                                        job[0], {"state": state}))
                self.jobs[c] = None
        self.done.extend((d.t_submit, d.t_done, d.commands, d.inp)
                         for d in out)
        return out

    def counters(self):
        return {"fake.calls": self.calls}

    def reference(self, inp, dtype):
        return {}

    def compare(self, inp, out, ref):
        self.checked.append(inp)
        return {"bad": 0}


def test_several_clients_finish_overlapping_jobs_in_one_call(monkeypatch):
    from repro.core import pim
    cell = FakeCell()
    spec = harness.Spec(cell={"name": "fake"}, config={}, traffic={
        "check_jobs": 3, "limits": {"bad": 0}}, family="fake",
        end_to_end=[], per_layer=[])
    monkeypatch.setattr(spec, "make_cell", lambda pim, seed: cell)
    res = harness.measure(spec, pim, 5, 0.1, False, time.perf_counter())
    run = res["run"]
    assert res["correct"], res["checks"]
    # every job the window's calls finished, with its own times
    assert run.jobs == [(t0, t1) for t0, t1, _, _ in cell.done]
    assert run.commands == sum(n for _, _, n, _ in cell.done)
    assert {n for _, _, n, _ in cell.done} == {100, 200, 300}
    assert res["attempted"] == len(run.jobs) > cell.calls
    # client 2's jobs span three calls, client 0's one: they overlap
    long = [d for d in cell.done if d[3][0] == 2]
    short = [d for d in cell.done if d[3][0] == 0]
    assert any(s[0] > d[0] and s[1] < d[1] for d in long for s in short)
    # the reservoir keeps client jobs, and the check compares those
    assert len(cell.checked) == 3
    assert set(cell.checked) <= {d[3] for d in cell.done}
    assert run.counters["fake.calls"] == cell.calls
    # an output the sample did not keep is freed before the next call
    assert cell.most_alive <= 3


@pytest.mark.parametrize("name", CELLS)
def test_single_client_cell_is_one_job_a_call(name, monkeypatch):
    from repro.core import pim
    per_call = []
    real = harness.JobCell.serve

    def serve(self, inp, span):
        done = real(self, inp, span)
        per_call.append(done)
        return done

    monkeypatch.setattr(harness.JobCell, "serve", serve)
    spec = tiny_spec(name)
    res = harness.measure(spec, pim, 2**33 + 3, 0.2, False,
                          time.perf_counter())
    run = res["run"]
    cell = res["cell"]
    window = per_call[len(harness.WARM):]          # after the two warm jobs
    assert [len(d) for d in per_call] == [1] * len(per_call)
    assert run.jobs == [(d.t_submit, d.t_done) for (d,) in window]
    assert all(t0 < t1 for t0, t1 in run.jobs)
    assert run.commands == cell.commands_per_job * len(run.jobs)
    assert res["attempted"] == len(run.jobs)
