"""The timed path broken underneath a run: ``correct`` comes out false
for each fault a serving cell can have, and for the control (the
reference one precision below the configuration's, in the program's
place).  CPU, smoke widths, the chip check skipped."""
import dataclasses

import numpy as np
import pytest

from conftest import run_smoke, smoke_cell


def answer_altered(dep, engine, controller):
    server = dep.server
    orig = server.apply_transforms

    def altered(raws, pred_names, plane=None):
        scores, bank, idx = orig(raws, pred_names, plane)
        scores = np.array(scores)
        scores[0] = min(1.0, scores[0] + 1e-3)
        return scores, bank, idx
    server.apply_transforms = altered


def raw_score_altered(dep, engine, controller):
    server = dep.server
    orig = server.run_models

    def altered(requests, idxs, pred_names, raw_cache=None, plane=None):
        raws = np.array(orig(requests, idxs, pred_names, raw_cache, plane))
        raws[:, 0] = np.clip(raws[:, 0] + 1e-3, 0.0, 1.0)
        return raws
    server.run_models = altered


def half_the_batch_left_out(dep, engine, controller):
    """The model stage scores the first half of each window and gives the
    rest the mean of those scores."""
    server = dep.server
    orig = server.run_models

    def half(requests, idxs, pred_names, raw_cache=None, plane=None):
        keep = max(1, len(idxs) // 2)
        raws = np.array(orig(requests, idxs[:keep], pred_names[:keep],
                             None, plane))
        rest = np.repeat(raws.mean(0, keepdims=True), len(idxs) - keep, 0)
        return np.concatenate([raws, rest]) if len(rest) else raws
    server.run_models = half


def state_unchanged(dep, engine, controller):
    """The track stage returns without staging anything."""
    dep.server.track = lambda *args, **kwargs: None


def refit_on_stale_samples(dep, engine, controller):
    """Calibration passes refit T^Q on the oldest three quarters of each
    stream's samples while reporting the stream's whole count."""
    orig = controller._snapshot

    def stale(streams, only=None):
        snaps, failures = orig(streams, only)
        return {k: dataclasses.replace(
            s, values=s.values[:max(1, 3 * len(s.values) // 4)])
            for k, s in snaps.items()}, failures
    controller._snapshot = stale


refit_on_stale_samples.refreshes = True


@pytest.mark.parametrize("fault", [answer_altered, raw_score_altered,
                                   half_the_batch_left_out, state_unchanged,
                                   refit_on_stale_samples])
def test_a_fault_makes_the_run_incorrect(fault):
    if getattr(fault, "refreshes", False):
        cell = smoke_cell("fleet48-internlm2.steady", "fleet48-internlm2-1.8b",
                          "steady", tenants=3, cap=8, refresh_every_s=0.2)
    else:
        cell = smoke_cell("tabular512.backlog", "tabular512-baf", "backlog",
                          tenants=3, cap=8)
    result = run_smoke(cell, seconds=0.5, tamper=fault)
    assert not result["correct"]
    failed = [k for k, v in result["checks"].items()
              if not v["value"] <= v["limit"]]
    assert failed, result["checks"]


def test_the_control_is_not_correct():
    result = run_smoke(smoke_cell("fleet48-internlm2.steady",
                                  "fleet48-internlm2-1.8b", "steady",
                                  tenants=3, cap=8, refresh_every_s=0.2),
                       seconds=0.5, control=True)
    assert result["correct"], result["checks"]
    assert result["info"]["refresh_tables_published"] > 0
    control = result["control_checks"]
    assert any(not v["value"] <= v["limit"] for v in control.values()), \
        control
    # the refit, computed one precision down, fails its own limit
    assert control["refit_gap"]["value"] > control["refit_gap"]["limit"]
