"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample
of the finished requests, drawn from the seed and holding the longest
one, goes through the plain reference once: the prompt with the served
tokens, teacher-forced, in float32.  At each served token the reading
is how far its reference logit lies below the reference's best logit
at that position; the number compared is the widest such gap.  Served
tokens are greedy, so a correct program reads only the rounding of its
bf16 arithmetic.

The control puts the reference, computed in a lower precision
(``refmath.QUANT``), in the program's place: at the same positions it
reads the gap of the token the lower precision puts first.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

T_BUCKET = 1024       # padded sequence lengths: few reference shapes
ROW_BUCKET = 128
# the control's precision: the configurations serve bf16, the next one
# below is 8 bits: fp8 (e4m3, weights and activations); int8 weights
# are read beside it (PERF.md section 2)
CONTROL = "fp8"


@jax.jit
def _served_gap(logits, tokens, n):
    best = logits.max(axis=-1)
    got = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    live = jnp.arange(logits.shape[0]) < n
    return jnp.where(live, best - got, 0.0).max()


@jax.jit
def _control_gap(ref_logits, ctl_logits, n):
    top = ctl_logits.argmax(axis=-1)
    return _served_gap(ref_logits, top, n)


def sample(finished: Sequence[Dict], seed: int, count: int) -> List[Dict]:
    """The longest finished request (prompt + output) and ``count - 1``
    others drawn from ``seed``."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (r["prompt_len"] + r["n_out"],
                                           -r["index"]))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng(seed)
    picked = rng.permutation(len(rest))[:count - 1]
    return [longest] + [rest[i] for i in picked]


def _shape(p: int, n: int):
    rows = -(-n // ROW_BUCKET) * ROW_BUCKET
    t = -(-max(p + n, p - 1 + rows) // T_BUCKET) * T_BUCKET
    return t, rows


def _padded(tokens: np.ndarray, t: int) -> np.ndarray:
    out = np.zeros((t,), np.int32)
    out[:len(tokens)] = tokens
    return out


def served_gap(ref, weights, conf: Dict, req: Dict) -> float:
    """Widest gap of one request's served tokens below the reference's
    best logit."""
    p, n = req["prompt_len"], req["n_out"]
    toks = np.asarray(req["output"], np.int32)
    t, rows = _shape(p, n)
    logits = ref.logits(weights, conf, _padded(toks, t), p - 1, rows)
    served = _padded(toks[p:], rows)
    return float(_served_gap(logits, jnp.asarray(served), n))


def control_gap(ref, weights, conf: Dict, req: Dict, quant: str) -> float:
    """Widest gap, at the same positions, of the token that the
    reference computed in ``quant`` puts first."""
    p, n = req["prompt_len"], req["n_out"]
    toks = np.asarray(req["output"], np.int32)
    t, rows = _shape(p, n)
    padded = _padded(toks, t)
    ref_logits = ref.logits(weights, conf, padded, p - 1, rows)
    ctl_logits = ref.logits(weights, conf, padded, p - 1, rows, quant=quant)
    return float(_control_gap(ref_logits, ctl_logits, n))


def readings(ref, weights, conf: Dict, picked: Sequence[Dict],
             quant: Optional[str] = None) -> Dict[str, float]:
    """The numbers compared, over the sampled requests: the widest
    served-token gap (or the control's, with ``quant``) and how many
    sampled requests returned another number of tokens than asked."""
    gaps = [control_gap(ref, weights, conf, r, quant) if quant
            else served_gap(ref, weights, conf, r) for r in picked]
    return {"served_logit_gap": max(gaps) if gaps else float("nan"),
            "wrong_length": float(sum(r["n_out"] != r["max_new_tokens"]
                                      for r in picked))}
