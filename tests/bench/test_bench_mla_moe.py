"""The DeepSeek-V2 family (``bench/families/mla_moe.py``) against the
program: its layout is the program's parameter tree at the published
sizes, its fields are the registry entry's with the held experts, its
work counts are the hand arithmetic at the published sizes, and a
DeepSeek-shaped toy served through the paged slot scheduler with chunked
admission gives the family reference's logits."""
import dataclasses
import json
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _bench_path import ROOT
from bench import weights
from bench.harness import Bench, model_config, run_cell

FAMILY = Bench().family("mla_moe")
CONF = json.loads((ROOT / "bench" / "configs"
                   / "deepseek-v2-lite-ep8.json").read_text())
SIZES = CONF["model"]

# one dense prefix layer, 2 MoE layers of 8 routed experts (4 held, top-3,
# no renormalization), latent rank 32, YaRN on at 64 original positions
TOY = dict(SIZES, hidden_size=64, intermediate_size=96,
           num_attention_heads=4, num_key_value_heads=4,
           num_hidden_layers=3, vocab_size=256, kv_lora_rank=32,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
           moe_intermediate_size=24, n_routed_experts=4,
           n_routed_experts_published=8, num_experts_per_tok=3,
           rope_scaling=dict(SIZES["rope_scaling"], factor=4,
                             original_max_position_embeddings=64))
TOY_ENGINE = {"paged": True, "scheduler": True, "method": "dense",
              "decode_sparse": False, "prefix_sharing": False,
              "prefill_chunk": 64, "max_batch": 2, "decode_extra": 32,
              "seq_buckets": [64, 128]}
TOY_CONF = {"name": "toy-mla", "source": "test", "family": "mla_moe",
            "registry": "deepseek-v2-lite", "model": TOY, "dtype": "float32",
            "share_prefill": {"block_size": 32, "min_seq_blocks": 2},
            "engine": TOY_ENGINE}


def test_layout_is_the_programs_tree_at_published_sizes():
    from repro.models import build_model
    from bench.weights import _path
    cfg = model_config(CONF, FAMILY)
    shape = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(shape)[0]
    got = {_path(kp): tuple(x.shape) for kp, x in flat}
    assert got == FAMILY.layout(SIZES)
    assert sum(int(np.prod(s)) for s in got.values()) == 3_110_989_312
    assert FAMILY.layout(SIZES)["stack/ffn/w_gate"] == (26, 8, 2048, 1408)
    assert FAMILY.layout(SIZES)["stack/ffn/router"] == (26, 2048, 64)


def test_model_fields_are_the_registry_entry_with_eight_held():
    from repro.configs import get_config
    reg = get_config("deepseek-v2-lite")
    cfg = model_config(CONF, FAMILY)
    assert cfg == dataclasses.replace(
        reg, moe=dataclasses.replace(reg.moe, num_held=8))
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.top_k) == (64, 8, 6)
    assert not cfg.moe.norm_topk and cfg.mla.rope_factor == 40.0
    with pytest.raises(ValueError, match="does not implement"):
        FAMILY.model_fields({"model": dict(SIZES, q_lora_rank=1536)})


def test_published_keys_stand_at_the_top_level():
    # the file carries the published config at its top level, and the
    # harness reads the same keys under "model": the two copies agree
    assert {k: CONF[k] for k in SIZES} == SIZES
    assert CONF["reduced"] == ["n_routed_experts"]
    assert (CONF["n_routed_experts"],
            CONF["n_routed_experts_published"]) == (8, 64)
    with pytest.raises(ValueError, match="differ from model"):
        FAMILY.model_fields(dict(CONF, first_k_dense_replace=None))


def test_work_counts_by_hand_at_published_sizes():
    # attention per layer: w_q 2048x16x192 + w_kv_down 2048x576
    # + w_uk 512x16x128 + w_uv 512x16x128 + wo 16x128x2048
    attn = 6_291_456 + 1_179_648 + 1_048_576 + 1_048_576 + 4_194_304
    assert attn == 13_762_560
    dense = attn + 3 * 2048 * 10944                       # 81,002,496
    # router 2048x64, shared 3x2048x2816, routed 6 x 8/64 = 0.75 experts
    moe = attn + 131_072 + 17_301_504 + 0.75 * 8_650_752  # 37,683,200
    p = FAMILY.matmul_params(SIZES)
    assert p == {"layers": dense + 26 * moe, "head": 209_715_200}
    assert p["layers"] == 1_060_765_696
    # a 32,768-token prompt: 256 x 257 / 2 causal 128-blocks per head
    # and layer, 2 x 128^2 x (192 + 128) each, 16 heads, 27 layers
    assert FAMILY.attention_flops(32_896, 128, SIZES) == \
        32_896 * 16 * 27 * 2 * 128 * 128 * 320 == 149_013_890_334_720
    assert FAMILY.key_flops(SIZES) == 2 * (576 + 512) * 16 * 27 == 940_032


def test_fill_at_toy_sizes():
    from repro.models import build_model
    cfg = model_config(TOY_CONF, FAMILY)
    shape = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    tree = weights.fill(shape, FAMILY, TOY, 3, jnp.float32)
    made = weights.make(FAMILY, TOY, 3, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(tree["stack"]["ffn"]["w_gate"]),
        np.asarray(made["stack/ffn/w_gate"]))
    assert float(jnp.std(made["stack/ffn/w_down"])) == pytest.approx(
        24 ** -0.5, rel=0.1)
    assert float(jnp.std(made["stack/attn/wo"])) == pytest.approx(
        (4 * 16) ** -0.5, rel=0.1)


@pytest.mark.parametrize("chunk", [64, 0])
def test_served_logits_match_the_reference(chunk):
    """Paged chunked prefill of two prompts of different buckets (64 and
    128 tokens, two 64-token quanta for the longer), then 9 decode steps
    with the two slots at different positions (and the same through
    one-shot admission): every first-token and
    decode logit row agrees with the float32 reference's full forward pass
    over prompt + served tokens within 2e-3.  Both sides run float32; they
    differ in summation order only (blocked vs chunked and paged
    attention, the absorbed latent decode, the grouped matmul's row
    order), 1.7e-6 here, so 2e-3 leaves room for other CPUs' sums, while
    renormalizing the top-k gates, or leaving YaRN's temperature out of
    the decode's softmax scale, moves them by 0.5."""
    from repro.models import build_model
    from repro.serving import EngineConfig, Request, ServingEngine
    from repro.serving import scheduler

    cfg = model_config(TOY_CONF, FAMILY)
    model = build_model(cfg)
    shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.fill(shape, FAMILY, TOY, 11, jnp.float32)
    sp = model.default_share_prefill()
    fields = {k: tuple(v) if isinstance(v, list) else v
              for k, v in TOY_ENGINE.items()}
    fields["prefill_chunk"] = chunk
    eng = ServingEngine(model, params, sp, EngineConfig(**fields))

    seen = []                      # (uid, row, logits)
    first = []                     # first-token logits, in admission order
    sample_token = scheduler.sample_token
    sample = scheduler.SlotScheduler._sample

    def first_rec(key, logits, cfg):
        first.append(np.asarray(logits[0]))
        return sample_token(key, logits, cfg)

    def sample_rec(self, occ, logits, logits_h, qs_h):
        for i in occ:
            seen.append((self.slots[i].req.uid, int(self.pos[i]),
                         logits_h[i].copy()))
        assert len(set(int(self.pos[i]) for i in occ)) == len(occ)
        return sample(self, occ, logits, logits_h, qs_h)

    rng = np.random.default_rng(0)
    reqs = [Request(uid=u, prompt=rng.integers(0, 256, n).astype(np.int32),
                    max_new_tokens=10) for u, n in ((0, 128), (1, 64))]
    mp = pytest.MonkeyPatch()
    mp.setattr(scheduler, "sample_token", first_rec)
    mp.setattr(scheduler.SlotScheduler, "_sample", sample_rec)
    try:
        eng.serve(reqs)
    finally:
        mp.undo()
    assert [r.state for r in reqs] == ["done", "done"]
    assert eng._chunk_tokens(128) == chunk
    seen += [(r.uid, len(r.prompt) - 1, lg) for r, lg in zip(reqs, first)]

    ref_params = weights.make(FAMILY, TOY, 11, jnp.float32)
    worst = 0.0
    for r in reqs:
        out = np.asarray(r.output_tokens)
        seq = np.concatenate([r.prompt, out[:-1]])
        ref = FAMILY.logits(ref_params, TOY, seq, np.arange(len(seq)),
                            pad=32, block=32)
        mine = [(row, lg) for uid, row, lg in seen if uid == r.uid]
        assert len(mine) == len(out)                 # first + 9 decode
        for row, lg in mine:
            worst = max(worst, float(np.abs(lg - ref[row]).max()))
        # every held expert took rows, prefill and decode
        assert r.expert_rows.shape == (4,) and (r.expert_rows > 0).all()
    assert worst < 2e-3, worst


def test_run_cell_on_the_cpu(tmp_path):
    """A whole run of a toy DeepSeek cell through the harness: the real
    family builds, fills, serves and checks it, and the run is correct."""
    home = tmp_path / "bench"
    for d in ("metrics", "families"):
        shutil.copytree(ROOT / "bench" / d, home / d)
    shutil.copy(ROOT / "bench" / "peaks.json", home / "peaks.json")
    for d in ("configs", "traffic", "limits"):
        (home / d).mkdir()
    (home / "configs" / "toy-mla.json").write_text(json.dumps(TOY_CONF))
    (home / "traffic" / "m.json").write_text(json.dumps(
        {"arrivals": "backlog", "rate_per_s": 1.5,
         "prompt_tokens": {"dist": "fixed", "tokens": 128},
         "output_tokens": {"dist": "log_uniform", "lo": 4, "hi": 8},
         "check_tokens": 16, "check_requests": 2}))
    (home / "limits" / "toy-mla.m.json").write_text(json.dumps(
        {"logit_gap": 0.01, "pad": 128}))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "toy-mla", "source": "test",
                        "file": "configs/toy-mla.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": "toy-mla.m", "config": "toy-mla",
                          "traffic": "m", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (tmp_path / "spec.json").write_text(json.dumps(spec))
    out = run_cell(Bench(tmp_path / "spec.json", home), "toy-mla.m", 5,
                   2.0, False, t_start=time.perf_counter())
    assert out["correct"] is True
    assert out["attempted"] == 3 and out["failed"] == 0
    assert out["check"]["logit_gap"]["value"] < 1e-3
