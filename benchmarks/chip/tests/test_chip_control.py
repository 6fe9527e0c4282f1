"""`correct` comes out false where it must: for the control (the reference
at the precision below the configuration's, in the program's place) and for
a fault planted in the timed path, at tiny sizes on the CPU."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.checkout(tmp_path_factory.mktemp("checkout"))


def control_lines(root, monkeypatch, cell, seeds, capsys):
    import control
    import run
    monkeypatch.setattr(run, "require_devices", lambda n: jax.devices()[:n])
    capsys.readouterr()
    assert control.main(["--workload", cell, "--seconds", "1", "--seeds",
                         *map(str, seeds)], root=root) == 0
    return [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if x.startswith("{")]


def test_serving_control_is_not_correct(root, monkeypatch, capsys):
    lim = json.loads((root / "benchmarks/chip/configs/tiny.json")
                     .read_text())["limits"]["logit_gap"]
    lines = control_lines(root, monkeypatch, "tiny-backlog", (1, 2, 3),
                          capsys)
    assert [r["seed"] for r in lines] == [1, 2, 3]
    for r in lines:
        assert r["program"]["correct"] is True
        assert r["control"]["correct"] is False
        assert r["program"]["logit_gap"] <= lim < r["control"]["logit_gap"]


def test_sweep_control_is_not_correct(root, monkeypatch, capsys):
    lim = json.loads((root / "benchmarks/chip/mixes/tiny-sweep.json")
                     .read_text())["limits"]["energy_rel_err"]
    lines = control_lines(root, monkeypatch, "tiny-sweep", (1, 2, 3), capsys)
    assert [r["seed"] for r in lines] == [1, 2, 3]
    for r in lines:
        assert r["program"]["correct"] is True
        assert r["control"]["correct"] is False
        assert r["program"]["energy_rel_err"] <= lim \
            < r["control"]["energy_rel_err"]
        assert r["program"]["counts_differ"] == 0


def test_control_correct_fails_the_tool(root, monkeypatch, capsys):
    """A control that comes out correct makes control.py exit non-zero."""
    import control
    import run
    monkeypatch.setattr(run, "require_devices", lambda n: jax.devices()[:n])
    real = run.driver

    def lenient(mix, here):
        drv = real(mix, here)

        class Twin:
            @staticmethod
            def run(ctx, control=False):
                res = drv.run(ctx, control=control)
                res["control_checks"] = res["checks"]
                return res
        return Twin
    monkeypatch.setattr(run, "driver", lenient)
    assert control.main(["--workload", "tiny-sweep", "--seconds", "1",
                         "--seeds", "1"], root=root) == 1


def test_altered_token_is_not_correct(root, monkeypatch):
    """Every emitted token moved to the next id where the decode chunk
    produces it."""
    import repro.serve.paged as paged
    orig = paged._decode_loop

    def altered(model, *a, **k):
        emitted, cache, tok, rem = orig(model, *a, **k)
        vocab = model.cfg.vocab_size
        return (jnp.where(emitted >= 0, (emitted + 1) % vocab, emitted),
                cache, (tok + 1) % vocab, rem)
    monkeypatch.setattr(paged, "_decode_loop", altered)
    out = tiny.run_cell(root, "tiny-backlog", monkeypatch=monkeypatch)
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("column,change", [
    (2, lambda v: v * (1 + 1e-6)),    # long-idle seconds
    (1, lambda v: v + 1.0),           # long-idle runs, a count
])
def test_altered_answer_is_not_correct(root, monkeypatch, column, change):
    """One statistic of the exact bank kernel altered where the Stage-II
    kernels produce it: the long-idle seconds by 1e-6 of themselves, or one
    more long-idle run on the last candidate that is not a tie."""
    import repro.kernels.bank_energy as be
    orig = be.exact_bank_stats

    def altered(*a, **k):
        s = np.array(orig(*a, **k), np.float64)
        if column == 2:
            s[:, 2] = change(s[:, 2])
        else:
            s[-1, 1] = change(s[-1, 1])
        return s
    monkeypatch.setattr(be, "exact_bank_stats", altered)
    out = tiny.run_cell(root, "tiny-sweep", monkeypatch=monkeypatch)
    assert out["correct"] is False
    if column == 1:
        assert out["checks"]["counts_differ"]["value"] > 0
