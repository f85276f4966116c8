"""A checkout-shaped directory holding one tiny cell, for CPU tests.

The tiny configuration keeps the Qwen2 block (GQA with 4 Q / 2 KV heads,
q/k/v biases, rotary embeddings, SwiGLU, tied embeddings) at widths the
Pallas interpreter runs in seconds.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

CONFIG = {
    "name": "tiny-qwen2", "source": "https://huggingface.co/Qwen/Qwen2.5-3B",
    "reference": "bench/reference/qwen2.py", "model_type": "qwen2",
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 512,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-06,
    "rope_theta": 1000000.0, "tie_word_embeddings": True,
    "torch_dtype": "bfloat16", "reduced": [],
}
ENGINE = {"attention_impl": "pallas", "split_policy": "paper",
          "use_scheduler_metadata": True, "prefill_mode": "fused",
          "cache_layout": "dense"}
TRAFFIC = {
    "open": {"loop": "open", "slots": 4, "max_len": 256,
             "prefill_bucket": 64, "seqlen_bucket": 64, "engine": ENGINE,
             "prompt_len": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
             "output_len": {"median": 24, "sigma": 0.5, "min": 8,
                            "max": 48},
             "arrivals": {"rate": 12.0, "cv": 2.0}, "pool_seed": 0},
    "closed": {"loop": "closed", "slots": 4, "max_len": 256,
               "prefill_bucket": 64, "seqlen_bucket": 64, "engine": ENGINE,
               "prompt_len": {"values": [64, 128]},
               "output_len": {"median": 24, "sigma": 0.5, "min": 8,
                              "max": 48},
               "queue": 64, "pool_seed": 0},
}


def make_root(tmp: Path, limit: float = 0.05) -> Path:
    """A directory laid out like a checkout: ``BENCHMARK.json`` and a
    copy of ``bench/`` with the tiny cells' files added."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-qwen2", "source": CONFIG["source"],
                         "file": "bench/configs/tiny-qwen2.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": f"tiny.{t}", "config": "tiny-qwen2", "traffic": f"tiny-{t}",
         "chips": 1, "why": "test"} for t in TRAFFIC]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench/configs/tiny-qwen2.json").write_text(json.dumps(CONFIG))
    for t, spec in TRAFFIC.items():
        (root / f"bench/traffic/tiny-{t}.json").write_text(json.dumps(spec))
        (root / f"bench/checks/tiny.{t}.json").write_text(
            json.dumps({"sample": 3, "max_logit_gap": limit}))
    return root
