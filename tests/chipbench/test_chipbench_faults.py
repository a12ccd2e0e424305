"""A whole run at CPU size with the chip check skipped: sound, it comes
out correct; with a served token altered where the engine samples it,
with a decode step that leaves the pool as it found it, or with the
control in the program's place, it comes out not correct."""
import json

import pytest

from chipbench import control, peaks, run, spec

CELLS = ("olmo-tiny.tiny",)


def run_cell(root, cell, capsys, monkeypatch, seed=12345678901,
             entry=run.main):
    # no published peaks for a CPU: rooflines are not read at this size
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"bf16_flops_per_s": 1e12,
                                             "hbm_bytes_per_s": 1e11})
    capsys.readouterr()
    rc = entry(["--workload", cell, "--seed", str(seed), "--seconds",
                "1.5", "--trace", "0"], root=root,
               bench_dir=root / "chipbench", require_tpu=False,
               compile_cache=False)
    assert rc == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root, cell, capsys, monkeypatch):
    root = tiny_root
    res, err = run_cell(root, cell, capsys, monkeypatch)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    bench = spec.load(root)
    assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"
    # each number compared is printed beside its limit, last on stderr
    assert err.strip().splitlines()[-1].startswith("check wrong_length:")


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token_is_not_correct(tiny_root, cell, capsys, monkeypatch):
    from repro.serving.engine import Engine
    sample = Engine._sample_rows

    def altered(self, logits, *a, **kw):
        tok = sample(self, logits, *a, **kw)
        return (tok + 1) % logits.shape[-1]       # a token off by one

    monkeypatch.setattr(Engine, "_sample_rows", altered)
    res, _ = run_cell(tiny_root, cell, capsys, monkeypatch)
    assert res["correct"] is False
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_stale_cache_is_not_correct(tiny_root, cell, capsys, monkeypatch):
    """A decode step that returns its state unchanged: it samples from
    the pool as it found it and drops the keys and values it wrote."""
    from repro.models import transformer as tf
    step = tf.decode_step

    def stale(p, cfg, token, caches, pos, **kw):
        logits, _ = step(p, cfg, token, caches, pos, **kw)
        return logits, caches

    monkeypatch.setattr(tf, "decode_step", stale)
    res, _ = run_cell(tiny_root, cell, capsys, monkeypatch)
    assert res["correct"] is False
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_root, cell, capsys, monkeypatch):
    """The reference in the control's precision, put in the program's
    place by ``control.py``, comes out not correct through the
    harness's own comparison; the sound reading of the same sample,
    which it prints beside, is within the limit."""
    res, err = run_cell(tiny_root, cell, capsys, monkeypatch,
                        entry=control.main)
    assert res["correct"] is False
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
    sound = [ln for ln in err.splitlines()
             if ln.startswith("sound served_logit_gap:")]
    assert len(sound) == 1
    assert float(sound[0].split(":")[1]) <= gap["limit"]
