"""``correct`` comes out false for the control and for each fault that a
cell can have, planted underneath the harness, and true for a sound run.

Every run here skips the harness's look for a chip and drives the rest of a
run on four CPU devices, at sizes a test run can hold.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

import harness
import run

SMALL = {"p2p": {"nbytes": 4 * 4096}, "jacobi": {"cols": 2048}}
PEAKS = harness.load_peaks("TPU v5 lite")


def run_small(name, seed=2**33 + 17, **kw):
    cell = harness.load_cell(name)
    cell = dataclasses.replace(
        cell, params={**cell.params, **SMALL[cell.driver]})
    line = run.run_cell(cell, jax.devices()[:cell.chips], seed=seed,
                        seconds=0.3, trace=False, peaks=PEAKS, **kw)
    return json.loads(line)


@pytest.mark.parametrize("name", ["p2p.diag.512M", "p2p.nbr.32B",
                                  "jacobi.1chip.2M"])
def test_sound_run_is_correct(name):
    out = run_small(name)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("name", ["p2p.diag.512M", "p2p.nbr.32B",
                                  "jacobi.1chip.2G"])
def test_control_is_not_correct(name):
    assert run_small(name, control=True)["correct"] is False


def _stale(send):
    last = {}

    def faulty(self, x, src, dst, **kw):
        out = send(self, x, src, dst, **kw)
        prev = last.get("out", out)
        last["out"] = out
        return prev
    return faulty


def _half(send):
    def faulty(self, x, src, dst, **kw):
        out = send(self, x, src, dst, **kw)
        n = out.shape[0] // 2
        return out.at[n:].set(0.0)
    return faulty


def _no_exchange(send):
    def faulty(self, x, src, dst, **kw):
        return x + 0.0
    return faulty


def _altered(send):
    def faulty(self, x, src, dst, **kw):
        out = send(self, x, src, dst, **kw)
        return out.at[3].add(1.0)
    return faulty


@pytest.mark.parametrize("fault", [_stale, _half, _no_exchange, _altered])
def test_send_fault_is_not_correct(monkeypatch, fault):
    from repro.comm.session import CommSession
    monkeypatch.setattr(CommSession, "send", fault(CommSession.send))
    assert run_small("p2p.diag.512M")["correct"] is False


def _unchanged(step):
    return lambda u, ax, **kw: u


def _half_cols(step):
    def faulty(u, ax, **kw):
        out = step(u, ax, **kw)
        n = u.shape[1] // 2
        return jnp.concatenate([out[:, :n], u[:, n:]], axis=1)
    return faulty


def _altered_cell(step):
    return lambda u, ax, **kw: step(u, ax, **kw).at[2, 5].add(1.0)


@pytest.mark.parametrize("fault", [_unchanged, _half_cols, _altered_cell])
def test_jacobi_fault_is_not_correct(monkeypatch, fault):
    from repro.core import halo
    monkeypatch.setattr(halo, "jacobi_step", fault(halo.jacobi_step))
    assert run_small("jacobi.1chip.2M")["correct"] is False
