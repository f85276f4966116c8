"""A cell -> the program's model, engine and weights.

What belongs to one architecture (its ``ModelConfig``, its weights in
the canonical and the program's layout) is in the cell's adapter,
``bench/adapters/<model_type>.py``.  This module holds what every
architecture shares: the engine settings a traffic file passes through,
the seed's PRNG key, the helpers that fill the program's param pytree,
and :func:`build`, the one construction path of every run.
"""
from __future__ import annotations

from typing import Any, Dict

import jax

# traffic "engine" keys passed through to the program's ServeConfig
ENGINE_KEYS = ("split_policy", "use_scheduler_metadata", "prefill_mode",
               "cache_layout", "kv_quant", "num_splits_override")


def serve_config(model_cfg, traffic: Dict[str, Any],
                 overrides: Dict[str, Any] | None = None):
    from repro.configs.base import ServeConfig
    engine = dict(traffic.get("engine", {}), **(overrides or {}))
    kw = {k: engine[k] for k in ENGINE_KEYS if k in engine}
    return ServeConfig(model=model_cfg, seqlen_bucket=traffic["seqlen_bucket"],
                       prefill_bucket=traffic["prefill_bucket"], **kw)


def tree_get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def tree_set(tree: Dict, path, value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def as_program_tree(flat: Dict) -> Any:
    """Nested dicts with integer keys -> the program's tuple groups."""
    if isinstance(flat, dict) and flat and all(isinstance(k, int)
                                                for k in flat):
        return tuple(as_program_tree(flat[i]) for i in range(len(flat)))
    if isinstance(flat, dict):
        return {k: as_program_tree(v) for k, v in flat.items()}
    return flat


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def check_layout(params, model) -> None:
    """Fail loudly if the program's param layout is not the one the
    adapter fills (a refactor of the model must update the adapter)."""
    want = jax.tree.map(lambda s: tuple(s.shape), model.abstract_params())
    got = jax.tree.map(lambda a: tuple(a.shape), params)
    if want != got:
        raise RuntimeError(f"program param layout changed:\n want {want}"
                           f"\n got {got}")


def build(cell, seed: int, devices, *, overrides=None, obs=None,
          hook=None):
    """The cell's engine with the seed's weights loaded, and the weights:
    the one construction path of every run, sweep and control.  A
    traffic file with ``"mesh": "<dp>,<sp>"`` gets the program's
    mesh-native engine over ``devices``; ``overrides`` replace the
    traffic file's engine settings; ``hook`` gets the engine before its
    weights are loaded (tests break it there)."""
    from repro.models.registry import build_model
    from repro.serving import ServingEngine
    from repro.serving.sampling import GreedySampler
    cfg, tr, adapter = cell.config, cell.traffic, cell.adapter
    engine_kw = dict(tr.get("engine", {}), **(overrides or {}))
    mcfg = adapter.model_config(cfg, engine_kw)
    model = build_model(mcfg)
    scfg = serve_config(mcfg, tr, overrides)
    kw = dict(max_len=tr["max_len"], sampler=GreedySampler(), obs=obs)
    if tr.get("mesh"):
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.shard import ShardedServingEngine, ShardSpec
        spec = ShardSpec.parse(tr["mesh"])
        spec = spec.with_(slots_per_shard=tr["slots"] // spec.dp)
        engine = ShardedServingEngine(model, scfg, spec=spec,
                                      devices=devices, **kw)
        # drawn straight into the replicated layout the engine loads,
        # so that no chip holds a second copy
        params = adapter.make_params(cfg, seed, sharding=NamedSharding(
            engine.plan.mesh, PartitionSpec()))
    else:
        engine = ServingEngine(model, scfg, batch_slots=tr["slots"], **kw)
        params = adapter.make_params(cfg, seed)
    check_layout(params, model)
    jax.block_until_ready(params)
    if hook is not None:
        hook(engine)
    engine.load(params)
    return engine, params
