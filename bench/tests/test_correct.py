"""What ``correct`` catches: the control (the reference at the next
precision below the deployment's, in the program's place) fails a limit
of every cell, and so does a run whose answers are altered where they are
produced, and an IVF run that probes too few lists.  All at a size a CPU
test can hold; the chip readings the limits were set from are in
PERF.md."""

import json

import numpy as np
import pytest

import control
import reference
import spec
from small import run_small
from test_rehearsal import CELLS

IVF_CELLS = [n for n in CELLS
             if spec.load_cell(n).config["index"]["kind"] == "ivf_flat"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_a_limit(name, capsys):
    out = run_small(name, controls={"high": reference.control_topk})
    assert out["correct"] is True, out["checks"]
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    (ctl,) = [ln for ln in lines if ln.get("phase") == "control"]
    assert all(c["ok"] for c in ctl["program"].values()), ctl
    assert ctl["correct"] is False, ctl


@pytest.mark.parametrize("name", CELLS)
def test_altered_answer_is_caught(name, monkeypatch):
    from repro.core import proxy as proxy_mod

    real = proxy_mod.Proxy.search

    def altered(self, *a, **kw):
        res = real(self, *a, **kw)
        res.pks = res.pks.copy()
        res.pks[:, 0] = np.where(res.pks[:, 0] >= 0, res.pks[:, 0] + 1, -1)
        return res

    monkeypatch.setattr(proxy_mod.Proxy, "search", altered)
    out = run_small(name)
    assert out["correct"] is False


@pytest.mark.parametrize("name", IVF_CELLS)
def test_fewer_probes_is_caught(name):
    out = run_small(name, fault=control.fewer_probes)
    assert out["checks"]["bad_answers"]["value"] == 0
    assert out["checks"]["score_err"]["value"] <= out["checks"]["score_err"]["limit"]
    assert out["correct"] is False, out["checks"]
