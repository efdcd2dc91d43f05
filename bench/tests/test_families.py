"""Configuration families, found by name (``bench/families``).

* The dense family is the benchmark's earlier, dense-only code moved: for
  both configurations its seeded parameter tree (at smoke size) and its
  costs equal, bit for bit, what the benchmark gave before families
  existed (values computed on that tree and written here).
* A family of another layer layout comes in as one new file: a toy
  mixture-of-experts family written to a temporary directory passes the
  program's layout check, makes its weights and serves as the reference,
  with no file of the benchmark changed.
"""

import hashlib
import os
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests import smoke  # noqa: F401  (puts src/ on the path)
from bench import families
from bench.harness import common, program, serve
from bench.reference import drdsgd
from bench.weights import flatten, make_params

#: (params sha256 at smoke size, matmul_params, param_count,
#:  train_flops_per_token(cfg, 2048), decode_step_cost(cfg, 32, 20000))
PARENT = {
    "qwen2-0.5b": (
        "f69b55e97edc6615f0a52cb8c75a67a457bf189bc6722237fb1c738aff54d3b5",
        493961216, 494032768, 3228137472.0, (33333837824.0, 2467651072.0)),
    "h2o-danube-1.8b": (
        "a6905f29be811e2c6fd64de849b497e8d8c740f3e201ac45c0b8eaafcf092d66",
        1749155840, 1831201280, 11250278400.0, (116861173760.0, 9454725120.0)),
}


def _config(name):
    return common.load_json(os.path.join(common.BENCH, "configs", name + ".json"))


def _smoke_size(cfg):
    return dict(cfg, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                vocab_size=256)


@pytest.mark.parametrize("name", sorted(PARENT))
def test_dense_family_keeps_the_parameters_and_costs(name):
    sha, matmul, count, train, decode = PARENT[name]
    cfg = _config(name)
    fam = families.load(cfg)
    assert cfg["family"] == "dense"
    p = flatten(make_params(_smoke_size(cfg), 2 ** 40 + 11))
    h = hashlib.sha256()
    for k in sorted(p):
        h.update(k.encode())
        h.update(np.asarray(p[k]).tobytes())
    assert h.hexdigest() == sha
    assert fam.matmul_params(cfg) == matmul
    assert families.param_count(cfg) == count
    assert fam.train_flops_per_token(cfg, 2048) == train
    assert fam.decode_step_cost(cfg, 32, 20000) == decode


def test_a_missing_family_names_its_file():
    with pytest.raises(FileNotFoundError, match="nosuch.py"):
        families.load({"name": "x", "family": "nosuch"})


TOY = textwrap.dedent('''
    """A toy mixture-of-experts family: every layer full attention and a
    routed expert MLP (a router and stacked expert weights)."""

    import types

    import jax.numpy as jnp
    import numpy as np


    def arch_config(cfg):
        from repro.models.config import ArchConfig, MoEConfig

        return ArchConfig(
            name=cfg["name"], arch_type="moe", n_layers=cfg["num_hidden_layers"],
            d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            d_ff=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
            ffn_pattern=("moe",), tie_embeddings=True,
            moe=MoEConfig(num_experts=cfg["num_experts"],
                          top_k=cfg["num_experts_per_tok"],
                          d_expert=cfg["moe_intermediate_size"]))


    def shapes(cfg):
        L, D, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
        H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
        return {
            "embedding.table": (V, D), "final_norm.scale": (D,),
            "groups.l0.norm1.scale": (L, D), "groups.l0.norm2.scale": (L, D),
            "groups.l0.mix.wq": (L, D, H, hd), "groups.l0.mix.wk": (L, D, KV, hd),
            "groups.l0.mix.wv": (L, D, KV, hd), "groups.l0.mix.wo": (L, H, hd, D),
            "groups.l0.ffn.router": (L, D, E),
            "groups.l0.ffn.experts.w_gate": (L, E, D, F),
            "groups.l0.ffn.experts.w_up": (L, E, D, F),
            "groups.l0.ffn.experts.w_down": (L, E, F, D),
        }


    def init(path, shape):
        if path.endswith("scale"):
            return "norm", 0.1
        return "normal", 1.0 / np.sqrt(shape[-2])


    def _logits_at(cfg, params, tokens, positions, dtype=jnp.float32):
        table = params["embedding"]["table"].astype(dtype)
        return (table[tokens[positions]] @ table.T).astype(jnp.float32)


    def _batch_loss(cfg, params, rows, dtype=jnp.float32):
        return jnp.mean(params["embedding"]["table"][rows].astype(dtype) ** 2)


    def reference():
        return types.SimpleNamespace(logits_at=_logits_at, batch_loss=_batch_loss)
''')


def test_a_new_family_is_one_new_file(tmp_path, monkeypatch):
    bench_files = sorted(os.path.join(d, f) for d, _, fs in os.walk(common.BENCH)
                         for f in fs if not f.endswith(".pyc"))
    before = {f: os.path.getmtime(f) for f in bench_files}
    (tmp_path / "toymoe.py").write_text(TOY)
    monkeypatch.setattr(families, "SEARCH", families.SEARCH + [str(tmp_path)])
    cfg = {"name": "toy", "family": "toymoe", "hidden_size": 32,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 8, "vocab_size": 64,
           "num_experts": 4, "num_experts_per_tok": 2,
           "moe_intermediate_size": 16}
    model = program.model(cfg)            # the program's layout check
    params = make_params(cfg, 3)
    assert flatten(params)["groups.l0.ffn.experts.w_gate"].shape == (2, 4, 32, 16)
    loss = model.loss(params, {"tokens": jnp.zeros((1, 9), jnp.int32)})
    assert np.isfinite(float(loss))
    toks = jnp.arange(10, dtype=jnp.int32)
    got = serve.reference_logits_fn(cfg, jnp.float32)(params, toks, jnp.arange(3))
    table = np.asarray(params["embedding"]["table"])
    np.testing.assert_allclose(np.asarray(got), table[:3] @ table.T, atol=1e-6)
    job = {"grad_clip": 1.0, "loss_clip": 10.0, "mu": 6.0}
    l, _, _ = drdsgd.node_grad(cfg, job, params, jnp.zeros((1, 5), jnp.int32))
    assert float(l) == pytest.approx(float(np.mean(table[0] ** 2)), rel=1e-6)
    after = sorted(os.path.join(d, f) for d, _, fs in os.walk(common.BENCH)
                   for f in fs if not f.endswith(".pyc"))
    assert after == bench_files
    assert all(os.path.getmtime(f) == t for f, t in before.items())
