"""Shared benchmark state: train the zoo + multiplexers once, cache to
results/bench_state/, and hand each table/figure benchmark the pieces
it needs.  Benchmarks therefore measure the SAME system the tests
exercise — no parallel implementations.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.configs.paper_mux import MuxExperimentConfig, config as full_config
from repro.core import mux_train
from repro.data.synthetic import image_dataset, make_templates

STATE_DIR = os.environ.get("REPRO_BENCH_STATE", "results/bench_state")


def bench_config() -> MuxExperimentConfig:
    """Sized for a single CPU core: enough steps for the zoo accuracy
    ordering to emerge, small enough to finish in minutes."""
    scale = os.environ.get("REPRO_BENCH_SCALE", "std")
    if scale == "full":
        return full_config()
    if scale == "smoke":
        return dataclasses.replace(full_config(), train_samples=1024,
                                   eval_samples=512, batch_size=64,
                                   zoo_steps=60, mux_steps=60)
    return dataclasses.replace(full_config(), train_samples=3072,
                               eval_samples=2048, batch_size=96,
                               zoo_steps=200, mux_steps=150)


def _data(cfg):
    key = jax.random.key(cfg.seed)
    kt, kd, ke = jax.random.split(key, 3)
    templates = make_templates(kt, num_classes=cfg.num_classes,
                               image_size=cfg.image_size)
    train_b = image_dataset(kd, templates, num_samples=cfg.train_samples,
                            batch=cfg.batch_size)
    eval_b = image_dataset(ke, templates, num_samples=cfg.eval_samples,
                           batch=cfg.batch_size)
    return train_b, eval_b


_CACHE: Dict[str, Any] = {}


def get_state(*, contrastive: bool = True) -> Dict[str, Any]:
    """Returns {cfg, zoo_state, mux_all, mux_pair, train_b, eval_b}."""
    tag = "cnt" if contrastive else "nocnt"
    if tag in _CACHE:
        return _CACHE[tag]
    cfg = bench_config()
    train_b, eval_b = _data(cfg)
    key = jax.random.key(cfg.seed + (0 if contrastive else 1))
    kz, km, kp = jax.random.split(key, 3)

    zoo_path = os.path.join(STATE_DIR, f"zoo_{tag}.npz")
    mux_path = os.path.join(STATE_DIR, f"mux_all_{tag}.npz")
    pair_path = os.path.join(STATE_DIR, f"mux_pair_{tag}.npz")

    t0 = time.time()
    zoo_state = mux_train.init_zoo_state(kz, cfg)
    if os.path.exists(zoo_path):
        zoo_state = ckpt.restore(zoo_path, jax.eval_shape(lambda: zoo_state))
    else:
        zoo_state = mux_train.train_zoo(kz, cfg, train_b,
                                        contrastive=contrastive, verbose=True)
        ckpt.save(zoo_path, zoo_state)

    pair = (cfg.mobile_model, cfg.cloud_model)
    mux_all = mux_train.init_mux_state(km, cfg)
    mux_pair = mux_train.init_mux_state(kp, cfg, names=pair)
    if os.path.exists(mux_path):
        mux_all = ckpt.restore(mux_path, jax.eval_shape(lambda: mux_all))
    else:
        mux_all = mux_train.train_mux(km, cfg, zoo_state, train_b, verbose=True)
        ckpt.save(mux_path, mux_all)
    if os.path.exists(pair_path):
        mux_pair = ckpt.restore(pair_path, jax.eval_shape(lambda: mux_pair))
    else:
        mux_pair = mux_train.train_mux(kp, cfg, zoo_state, train_b, names=pair,
                                       verbose=True, objective="offload")
        ckpt.save(pair_path, mux_pair)

    state = {"cfg": cfg, "zoo_state": zoo_state, "mux_all": mux_all,
             "mux_pair": mux_pair, "train_b": train_b, "eval_b": eval_b,
             "train_s": time.time() - t0}
    _CACHE[tag] = state
    return state


def eval_zoo(state) -> Dict[str, Any]:
    """Per-model accuracy + correctness matrix over the eval set."""
    cfg = state["cfg"]
    names = list(cfg.zoo)
    correct_rows: List[np.ndarray] = []
    labels_all: List[np.ndarray] = []
    probs_all: List[np.ndarray] = []
    weights_all: List[np.ndarray] = []
    weights_pair: List[np.ndarray] = []
    hardness: List[np.ndarray] = []
    from repro.core.multiplexer import mux_forward
    for b in state["eval_b"]:
        probs, embeds, logits = mux_train.zoo_apply(state["zoo_state"],
                                                    b["image"], names)
        correct = np.stack([np.asarray(jnp.argmax(logits[n], -1) == b["label"])
                            for n in names])
        correct_rows.append(correct)
        labels_all.append(np.asarray(b["label"]))
        probs_all.append(np.asarray(probs))
        w_all, _ = mux_forward(state["mux_all"], b["image"])
        weights_all.append(np.asarray(w_all))
        w_pair, _ = mux_forward(state["mux_pair"], b["image"])
        weights_pair.append(np.asarray(w_pair))
        hardness.append(np.asarray(b["hardness"]))
    return {
        "names": names,
        "correct": np.concatenate(correct_rows, axis=1),   # (N, B_total)
        "labels": np.concatenate(labels_all),
        "probs": np.concatenate(probs_all, axis=1),        # (N, B_total, C)
        "weights_all": np.concatenate(weights_all, axis=0),
        "weights_pair": np.concatenate(weights_pair, axis=0),
        "hardness": np.concatenate(hardness),
    }


def emit(name: str, us_per_call: float, derived: str):
    """The scaffold's CSV contract: name,us_per_call,derived."""
    print(f"{name},{us_per_call:.1f},{derived}")
