"""Small configurations and jobs for the benchmark's CPU tests: the cells'
code paths at a size a test run holds."""

from __future__ import annotations

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.harness import common  # noqa: E402

#: qwen2-like: QKV bias, tied head, full attention
QWEN = {"name": "smoke-qwen", "family": "dense", "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
        "rms_norm_eps": 1e-6, "rope_theta": 1e6, "attention_bias": True,
        "tie_word_embeddings": True, "use_sliding_window": False,
        "sliding_window": 4096, "torch_dtype": "float32"}

#: danube-like: no bias, own head, every layer windowed (window < context)
DANUBE = dict(QWEN, name="smoke-danube", attention_bias=False,
              tie_word_embeddings=False, use_sliding_window=True,
              sliding_window=16, rms_norm_eps=1e-5, rope_theta=1e4)

SERVE_JOB = {"kind": "serve", "schedule_seed": 0, "rate": 20.0,
             "classes": [{"name": "a", "prompt_len": 12, "weight": 3,
                          "gen_min": 4, "gen_max": 12},
                         {"name": "b", "prompt_len": 24, "weight": 1,
                          "gen_min": 4, "gen_max": 12}],
             "max_batch": 4, "page_size": 4, "max_len": 40, "kv_pool": "f32",
             "check_requests": 4}


def train_job() -> dict:
    job = common.load_json(os.path.join(common.BENCH, "traffic",
                                        "train-k2-complete.json"))
    return dict(job, seq_len=64)


def limits(workload: str) -> dict:
    return common.load_json(os.path.join(common.BENCH, "limits",
                                         workload + ".json"))


def spec(cfg: dict, job: dict, lim: dict, *, seed: int = 2 ** 40 + 11,
         seconds: float = 1.0) -> common.Spec:
    e2e = [{"name": n} for n in ("train_tokens_per_s", "serve_output_tokens_per_s",
                                 "ttft_p95_ms", "tpot_p95_ms", "setup_s")]
    return common.Spec(workload="smoke", cfg=copy.deepcopy(cfg),
                       job=copy.deepcopy(job), limits=dict(lim),
                       end_to_end=e2e, per_layer=[], chips=1, seed=seed,
                       seconds=seconds, trace=False)


def devices():
    import jax

    return jax.devices()[:1]
