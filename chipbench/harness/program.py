"""Everything the benchmark takes from the program under test, in one place.

The program is ``src/repro``: its paged engine and weight store, and for
each architecture its ``ModelConfig`` and parameter layout, which the
architecture's adapter (``chipbench/adapters/<architecture>.py``) builds.
``adapter`` and ``reference`` find an architecture's two modules by the
configuration's ``architecture``.  The engine has no public accessor of
per-request progress, so ``active`` and ``finished`` read each request's
state (its tokens, logps and prefill progress) without changing it.
"""
from __future__ import annotations

import importlib
from types import ModuleType
from typing import Dict, Iterator

import jax


class UnknownArchitecture(LookupError):
    """A configuration names an architecture without both of its modules."""

    def __init__(self, architecture: str):
        super().__init__(
            f"no architecture {architecture!r}: it needs "
            f"chipbench/adapters/{architecture}.py and "
            f"chipbench/reference/{architecture}.py")


def _lookup(package: str, config: Dict) -> ModuleType:
    name = f"chipbench.{package}.{config['architecture']}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise UnknownArchitecture(config["architecture"]) from None


def adapter(config: Dict) -> ModuleType:
    """The architecture's adapter (``chipbench/adapters``): its
    ``model_config``, ``to_program_params`` and counts, ``Shapes``."""
    return _lookup("adapters", config)


def reference(config: Dict) -> ModuleType:
    """The architecture's plain reference (``chipbench/reference``): its
    ``init_weights`` and ``logits_at``."""
    return _lookup("reference", config)


def check_layout(params: Dict, mcfg) -> None:
    """Fail early, with both layouts named, if the program's parameter
    layout is not the one the adapter's ``to_program_params`` builds."""
    from repro.models.api import get_model
    want = jax.eval_shape(lambda: get_model(mcfg).init(jax.random.PRNGKey(0), mcfg))
    got = jax.eval_shape(lambda: params)
    shape = lambda t: jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), t)
    if shape(want) != shape(got):
        raise RuntimeError(f"parameter layout differs from the program's: "
                           f"{shape(got)} vs {shape(want)}")


def make_task(prompt):
    from repro.data.tasks import MathTask
    return MathTask(prompt="", answer=0, prompt_ids=list(prompt))


def active(engine) -> Iterator:
    """The requests that hold a slot, read only."""
    return iter(list(engine._active.values()))


def finished(engine, since: int = 0) -> list:
    """The requests finished since the ``since``-th, read only."""
    return engine._done[since:]


def prefilling(engine) -> int:
    """Requests that hold a slot and still prefill (or wait to fork)."""
    return sum(r.state in ("PREFILL", "FORK") for r in active(engine))


def queued(engine) -> int:
    return engine.pending - (engine.serve.max_slots - engine.kv.free_slots)
