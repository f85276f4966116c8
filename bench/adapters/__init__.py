"""Architecture adapters: what the harness knows of one architecture.

A configuration file names its published ``model_type``, and
``bench.harness.cell.load_cell`` loads ``bench/adapters/<model_type>.py``
by path, so that a new architecture is added as new files.  An adapter
owns the two weight layouts, the canonical one (per layer, the names the
plain reference reads) and the program's param pytree, and provides:

- ``dims(cfg) -> dict``: the sizes the harness and the metrics read,
  with at least ``model_type``, ``layers`` and ``vocab``;
- ``model_config(cfg, engine)``: the program's ``ModelConfig`` at the
  configuration's published widths, with ``engine["attention_impl"]``;
- ``make_params(cfg, seed, dtype=jnp.bfloat16, sharding=None)``: the
  program's param pytree drawn from ``seed`` on the device in one jitted
  call, in the dtype it is served in (into ``sharding`` where given);
- ``canonical_layer(params, cfg, i)`` and ``canonical_globals(params,
  cfg)``: the served weights of layer ``i`` and outside the layers, in
  the canonical layout the reference takes;
- the counts the per-layer metrics use (``bench/metrics/_lib.py``):
  ``layer_matmul_params(n)``, ``decode_token_flops(n, kv_len)`` and
  ``flash_decode_row(n)``, from ``n = dims(cfg)``; the readers get these
  dims with the adapter under ``"adapter"`` (``Cell.dims``), and count
  through it.
"""
