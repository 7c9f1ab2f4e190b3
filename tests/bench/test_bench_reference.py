"""The plain reference at a smoke size on the CPU: it remakes the program's
weights from the seed without taking them, it agrees with the program's
prefill within bfloat16 rounding, and its float8 control fails the
limits ``correct`` holds the configurations to."""

import numpy as np
import pytest

from bench import check, harness, spec
from repro.serving.real_model import RealModel

BLOCK = 16
SEED = 2**31 + 17  # a seed past 32 signed bits: runs are given such seeds


@pytest.fixture(scope="module", params=["qwen3-14b", "glm4-9b"])
def setup(request):
    cfg = harness.rehearsal_config(spec.config(request.param))
    fam = spec.family(cfg)
    model = RealModel(fam.model_config(cfg), BLOCK, seed=SEED)
    return request.param, cfg, model, fam, fam.init_weights(cfg, SEED)


def test_weights_remade_bit_for_bit(setup):
    _, _, model, _, w = setup
    p = model.params
    prog = {"embed": p["embed"], "final_norm": p["final_norm"], "lm_head": p["lm_head"],
            "attn_norm": p["blocks"]["attn_norm"], "mlp_norm": p["blocks"]["mlp_norm"],
            **p["blocks"]["attn"], **p["blocks"]["mlp"]}
    assert set(prog) == set(w)
    for name, arr in prog.items():
        assert arr.dtype == w[name].dtype and arr.shape == w[name].shape, name
        assert np.array_equal(np.asarray(arr).view(np.uint16), np.asarray(w[name]).view(np.uint16)), name


def _numbers(cfg, model, fam, w, fp8):
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg["vocab_size"], 96).tolist()
    rows, _ = fam.forward(cfg, w, toks)
    L = cfg["num_hidden_layers"]
    if fp8:
        c = fam.forward(cfg, w, toks, fp8=True)[0].astype(np.float16)
        blocks = [c[i * BLOCK:(i + 1) * BLOCK] for i in range(len(toks) // BLOCK)]
    else:
        blocks, _ = model(toks, 0)
    kv = check.worst_block_error(blocks, rows, 0, fam.block_parts(cfg), L, BLOCK)
    out, logits = model.generate(toks, 4)
    seq = toks + out
    _, ref = fam.forward(cfg, w, seq, head_rows=[len(seq) - 1])
    if fp8:
        _, got = fam.forward(cfg, w, seq, head_rows=[len(seq) - 1], fp8=True)
    else:
        got = np.asarray(logits, np.float32).reshape(-1)
    return kv, check.relative_error(got, ref)


def test_program_within_and_control_beyond_the_limits(setup):
    name, cfg, model, fam, w = setup
    lim = spec.limits(name)
    kv, logit = _numbers(cfg, model, fam, w, fp8=False)
    assert kv <= lim["kv_prefill_rel_err"]["max"]
    assert logit <= lim["decode_logits_rel_err"]["max"]
    ckv, clogit = _numbers(cfg, model, fam, w, fp8=True)
    assert ckv > lim["kv_prefill_rel_err"]["max"] or clogit > lim["decode_logits_rel_err"]["max"]
    assert ckv > 3 * kv and clogit > 3 * logit


def test_block_layout_and_missing_blocks():
    K = np.arange(2 * 32 * 2 * 4, dtype=np.float32).reshape(2, 32, 2, 4) + 1
    V = -K
    gqa = spec.family({})
    rows = gqa.kv_rows(K, V)
    # row t holds (k|v, layer, head, dim) of token t
    assert rows.shape == (32, 2 * 2 * 2 * 4)
    assert np.array_equal(rows[5, :8], K[0, 5].ravel())
    assert np.array_equal(rows[5, 16:24], V[0, 5].ravel())
    parts = gqa.block_parts({"num_key_value_heads": 2, "head_dim": 4})
    assert parts == [("k", 8), ("v", 8)]
    blocks = [rows[i * 16:(i + 1) * 16].astype(np.float16) for i in range(2)]
    assert check.worst_block_error(blocks, rows, 0, parts, 2, 16) < 1e-3
    assert check.worst_block_error([blocks[0], None], rows, 0, parts, 2, 16) == 1.0
    assert check.worst_block_error([blocks[0] * 1.5], rows, 0, parts, 2, 16) == pytest.approx(0.5, 1e-3)
