"""A benchmark root at CPU size, for the chipbench tests: a copy of
``chipbench/`` beside a ``BENCHMARK.json`` whose cell runs a two-layer
olmo-1b, cut in the configuration file's ``reduced``, for a couple of
seconds."""
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# The tiny cell's limit, set as the cell's own is (PERF.md section 2)
# from a dozen seeds on the CPU: sound at most 0.0041, the fp8 control
# at least 0.0122.
TINY = {
    "olmo-tiny": {
        "base": "olmo-1b", "limit": 0.008,
        "cut": {"num_layers": 2, "d_model": 64, "d_ff": 128,
                "vocab_size": 512, "num_heads": 4, "num_kv_heads": 4,
                "head_dim": 16}},
}
SERVING = {"dtype": "bfloat16", "page_size": 16, "num_pages": 64,
           "decode_batch": 4, "prefill_chunk_pages": 2}
MIX = {"clients": 4, "lead_in_s": 1.0, "stagger": True, "block": 8,
       "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                  "min": 8, "max": 60},
       "output": {"dist": "uniform", "min": 4, "max": 12}}


def make_root(tmp: Path) -> Path:
    """``tmp`` with BENCHMARK.json and a copy of chipbench/ holding the
    tiny configuration and the mix ``tiny``."""
    shutil.copytree(REPO / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"], bench["workloads"] = [], []
    for name, t in TINY.items():
        conf = json.loads((REPO / "chipbench" / "configs"
                           / f"{t['base']}.json").read_text())
        conf.update(t["cut"], name=name, serving=SERVING, check_requests=3,
                    limits={"served_logit_gap": t["limit"],
                            "wrong_length": 0})
        conf["reduced"] = dict(conf["reduced"], **{k: "cut for the CPU"
                                                  for k in t["cut"]})
        path = f"chipbench/configs/{name}.json"
        (tmp / path).write_text(json.dumps(conf))
        bench["configs"].append({"name": name, "source": conf["source"],
                                 "file": path, "reduced": [], "why": "test"})
        bench["workloads"].append({"name": f"{name}.tiny", "config": name,
                                   "traffic": "tiny", "chips": 1,
                                   "why": "test"})
    (tmp / "chipbench" / "traffic" / "tiny.json").write_text(json.dumps(MIX))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
