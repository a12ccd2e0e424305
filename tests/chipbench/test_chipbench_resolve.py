"""Every cell and metric of BENCHMARK.json resolves to its files by
name, and a cell added in a directory of its own needs no edit of an
existing file."""
import json
import re
from pathlib import Path

import pytest

from chipbench import run, spec

REPO = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load(REPO)


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert {m["name"] for m in bench["end_to_end"]} >= {"setup_s"}
    assert all(0.01 <= m["bound"] <= 0.25 for m in bench["end_to_end"])
    assert all((REPO / p).is_dir() for p in bench["paths"])
    layers = {m["layer"] for m in bench["per_layer"]}
    assert layers <= {"scheduler", "engine", "model", "kernels", "device"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert all(m["moves"] in e2e for m in bench["per_layer"])


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        conf = spec.config(bench, w["config"], REPO)
        assert conf["name"] == w["config"]
        spec.traffic(w["traffic"])
        ref = spec.reference(conf["family"])
        assert callable(ref.logits) and callable(ref.to_program)
        assert set(conf["limits"]) == {"served_logit_gap", "wrong_length"}
        assert run.model_config(conf).name == conf["arch"]


def test_every_metric_resolves(bench):
    for m in bench["per_layer"]:
        assert callable(spec.metric(m["name"]).read)


def test_config_file_must_match_program(bench, tmp_path):
    conf = spec.config(bench, "olmo-1b", REPO)
    conf["d_ff"] = 4096
    with pytest.raises(ValueError, match="d_ff"):
        run.model_config(conf)
    conf["reduced"] = {"d_ff": 8192}
    assert run.model_config(conf).d_ff == 4096


def test_new_cell_in_its_own_files(tiny_root):
    """The tiny root adds configurations, a mix and, here, a metric,
    each as a file of its own beside a copy of the benchmark: every
    existing file is byte for byte the repository's."""
    bench = spec.load(tiny_root)
    bench_dir = tiny_root / "chipbench"
    (bench_dir / "metrics" / "requests_sent.py").write_text(
        "def read(rec):\n"
        "    return float(sum(rec.w0 <= r['sent'] < rec.w1"
        " for r in rec.requests))\n")
    bench["per_layer"].append({"name": "requests_sent", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "tokens_per_s"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    for path in (REPO / "chipbench").rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            twin = bench_dir / path.relative_to(REPO / "chipbench")
            assert twin.read_bytes() == path.read_bytes(), path
    cell = spec.cell(spec.load(tiny_root), "olmo-tiny.tiny")
    conf = spec.config(spec.load(tiny_root), cell["config"], tiny_root)
    assert conf["num_layers"] == 2
    assert spec.traffic(cell["traffic"], bench_dir)["clients"] == 4
    assert [m["name"] for m in spec.per_layer(spec.load(tiny_root),
                                              cell["name"])][-1] == \
        "requests_sent"
    assert spec.metric("requests_sent", bench_dir).read is not None


def test_no_tpu_exits_nonzero_without_result(capsys):
    rc = run.main(["--workload", "olmo-1b.conversation-closed", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == run.NO_CHIP
    assert capsys.readouterr().out == ""
