"""``correct`` comes out false when the timed path is broken underneath.

Each test skips the harness's look for a chip and drives the rest of a
run (set-up, window, reference, comparison) on the CPU at a tiny size,
with one fault planted in the program: an answer altered where it is
produced, half of the work's input left out, and a step that returns
its state unchanged.  The cell runs on one chip, so there is no
exchange between chips to leave out.  The sound run must come out
correct.
"""
import copy
import pathlib

import jax
import numpy as np
import pytest

from benchmarks.chip import spec
from benchmarks.chip.run import Run, execute

ROOT = pathlib.Path(__file__).resolve().parents[3]
TINY_GRAPH = {"n_u": 256, "n_v": 128, "m_target": 1200}


def tiny(name):
    cell = spec.load_cell(name, ROOT)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.traffic["graph"].update(TINY_GRAPH)
    return cell


def run_cell(name, seconds=2.0):
    cell = tiny(name)
    run = Run(cell, 2**31 + 77, seconds, jax.devices()[:1],
              backends=("xla",))
    run.listen()
    return execute(run, spec.kind_module(cell.traffic["kind"]), traced=False)


def failed_checks(out):
    return {n for n, v, lim in out["checks"] if v > lim}


def test_static_sound_run_is_correct():
    out = run_cell("static-dense")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def _wrap_decompose(monkeypatch, change_theta=None, change_graph=None):
    from repro.api import executor

    original = executor.Executor.decompose

    def decompose(self, graph, *args, **kw):
        if change_graph is not None:
            graph = change_graph(graph)
        dec = original(self, graph, *args, **kw)
        if change_theta is not None:
            dec.theta = change_theta(np.array(dec.theta))
        return dec

    monkeypatch.setattr(executor.Executor, "decompose", decompose)


def _altered(theta):
    theta[len(theta) // 2] += 1
    return theta


def _half_edges(graph):
    from repro.core.graph import BipartiteGraph

    keep = slice(0, graph.m // 2)
    return BipartiteGraph.from_edges(graph.n_u, graph.n_v,
                                     graph.edges_u[keep], graph.edges_v[keep])


@pytest.mark.parametrize("fault", [
    dict(change_theta=_altered),                      # answer altered
    dict(change_graph=_half_edges),                   # half the input
    dict(change_theta=np.zeros_like),                 # state unchanged
], ids=["answer_altered", "half_left_out", "state_unchanged"])
def test_static_fault_is_caught(monkeypatch, fault):
    _wrap_decompose(monkeypatch, **fault)
    out = run_cell("static-dense")
    assert not out["correct"]
    assert "theta_mismatch" in failed_checks(out)


def test_bfloat16_reference_control_is_caught(monkeypatch):
    """The control: the reference with bfloat16 supports in the
    program's place (``control.py --control bfloat16-reference``)."""
    from benchmarks.chip.control import reference_in_program_place
    from repro.api import executor

    # restored after the test
    monkeypatch.setattr(executor.Executor, "decompose",
                        executor.Executor.decompose)
    reference_in_program_place()
    out = run_cell("static-dense", seconds=3.0)
    assert not out["correct"]
    assert "theta_mismatch" in failed_checks(out)
