# Test tiers (markers registered in pytest.ini):
#   make verify      fast tier, < 5 min — plan-golden gate + serving A/B
#                    smoke first, then everything not marked
#                    slow/multidevice
#   make verify-all  the full tier-1 suite (what the roadmap's verify line runs)
#   make bench       every benchmark (one per paper table/figure + serving A/B)

PY := PYTHONPATH=src python

.PHONY: verify verify-all bench golden plan-golden tune-golden \
	serving-smoke cache-smoke prefix-smoke tune-smoke spec-smoke \
	quant-smoke shard-smoke

verify: plan-golden tune-golden serving-smoke cache-smoke prefix-smoke \
	tune-smoke spec-smoke quant-smoke shard-smoke
	$(PY) -m pytest -q -m "not multidevice and not slow"

# seconds-scale serving A/B: fused-prefill admission must stay O(1)
# planned launches per request (structural counters, not timing)
serving-smoke:
	$(PY) -m benchmarks.serving_ab --smoke

# seconds-scale cache-layout A/B: paged must match dense greedy tokens
# bit-exact while allocating/streaming fewer cache bytes (structural)
cache-smoke:
	$(PY) -m benchmarks.cache_ab --smoke

# seconds-scale prefix-sharing A/B: share_prefix must match the
# unshared engine's greedy tokens bit-exact while full-prefilling only
# the leader (followers admit as suffix launches on adopted pages) and
# allocating strictly fewer pages (structural counters + conservation)
prefix-smoke:
	$(PY) -m benchmarks.prefix_ab --smoke

# seconds-scale speculative-decoding A/B: greedy tokens bit-identical
# with speculation on/off, oracle drafter accepts ~all and emits > 1
# token per planned verify launch, page conservation after the
# reject-heavy cell (structural counters, not timing)
spec-smoke:
	$(PY) -m benchmarks.spec_ab --smoke

# seconds-scale quantized-KV A/B: fused int8 never modeled-slower than
# dequant-then-attend, fused==unfused within per-dtype tolerance
# (int8 + fp8, poisoned tails, dense + paged), int8 engine streams
# identical across the serving matrix (structural, not timing)
quant-smoke:
	$(PY) -m benchmarks.quant_ab --smoke

# seconds-scale mesh-native serving A/B: dp=4 slot shards serve 4x the
# single engine's slots and sp=4 sequence-shards decode over 4 chips,
# both with bit-identical greedy tokens, mesh_splits provenance on the
# sp plans, per-shard launch counters, and zero traced policy evals
# (re-execs itself under 8 forced host devices)
shard-smoke:
	$(PY) -m benchmarks.shard_ab --smoke

# seconds-scale tuning A/B: measured policy never slower than the
# analytic policies on covered shapes, counted paper fallback elsewhere,
# serving engine end-to-end on split_policy=measured (structural)
tune-smoke:
	$(PY) -m benchmarks.tune_ab --smoke

verify-all:
	$(PY) -m pytest -q

bench:
	$(PY) -m benchmarks.run

# fast gate: the Planner must reproduce the committed golden decision
# table bit-exact (plan-API drift fails here before the full tier runs)
plan-golden:
	$(PY) -m pytest -q tests/test_policy_golden.py \
	    tests/test_plan.py::test_planner_reproduces_golden_table_bit_exact

# fast gate (mirrors plan-golden for repro.tune): the committed
# reference SplitTable must be schema-valid and replay bit-exact
# through Planner(policy="measured"); regenerate intentionally with
# `python -m repro.launch.tune --reference` and commit the diff
tune-golden:
	$(PY) -m pytest -q \
	    tests/test_tune.py::test_reference_table_schema_valid \
	    tests/test_tune.py::test_reference_table_replays_bit_exact \
	    tests/test_tune.py::test_reference_table_is_regenerated_deterministically

# regenerate the policy decision golden table (commit the diff!)
golden:
	$(PY) tests/test_policy_golden.py --regen
