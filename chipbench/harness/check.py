"""The comparison that decides ``correct`` for a served model.

For a sample of served requests the reference runs once over each prompt
with its served tokens.  One number is compared, over every served token
of the sample:

``logp_err_max``   the widest distance between the behaviour logp the
                   engine reported for a served token and the reference's
                   log-probability of that token at that position.

The traffic samples its tokens (temperature 1), so which token was served
says little; the logp the engine reports with it is what GRPO's ratios use,
and it is the program's whole next-token distribution read at that token.
A token altered after sampling, a cache that lost its keys and a row that
was not computed all move it.

The control puts the reference computed in float8 in the program's place:
its log-probability of the same served token at the same position.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("logp_err_max",)


@jax.jit
def _logp(logits, served):
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0] - lse


def readings(reference, weights, config: Dict, samples: Sequence[Dict], *,
             control: bool = False) -> Dict[str, float]:
    """``samples``: dicts with ``prompt``, ``tokens`` (served) and ``logps``
    (the engine's behaviour logps of ``tokens``).  Returns the program's
    numbers, or the control's with ``control=True``."""
    err = []
    for s in samples:
        prompt, tokens = list(s["prompt"]), list(s["tokens"])
        seq = prompt + tokens[:-1]
        rows = np.arange(len(prompt) - 1, len(seq))
        served = jnp.asarray(tokens, jnp.int32)
        ref = np.asarray(_logp(reference.logits_at(weights, config, seq, rows),
                               served), np.float64)
        got = (_logp(reference.logits_at(weights, config, seq, rows, fp8=True),
                     served) if control else s["logps"])
        err.append(np.abs(np.asarray(got, np.float64) - ref))
    err = np.concatenate(err)
    return {"logp_err_max": float(np.max(err)) if err.size else float("nan"),
            "served_tokens": int(err.size)}


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number present, finite and within its limit."""
    return all(np.isfinite(values.get(k, np.nan)) and values[k] <= limits[k]
               for k in NAMES)


def sample(requests: List[Dict], n: int, rng: np.random.Generator) -> List[Dict]:
    """The longest request and ``n - 1`` others drawn from ``rng``."""
    if len(requests) <= n:
        return list(requests)
    order = sorted(range(len(requests)),
                   key=lambda i: -(len(requests[i]["prompt"])
                                   + len(requests[i]["tokens"])))
    rest = rng.choice(order[1:], size=n - 1, replace=False)
    return [requests[order[0]]] + [requests[i] for i in sorted(rest)]
