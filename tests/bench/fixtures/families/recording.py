"""A model family for the tests, copied into ``bench/families/``: the GQA
family's answers with every call recorded, fixed FLOPs and scope costs, and
the reference rows scaled by ``ROW_SCALE``."""

from bench import spec

gqa = spec.family({})
CALLS = []
FLOPS_PER_CALL = 1000.0
SCOPE_COST = (10.0, 20.0)  # flops, bytes of the "attention" scope per prefill
ROW_SCALE = 1.0


def model_config(cfg):
    CALLS.append("model_config")
    return gqa.model_config(cfg)


def rehearsal_config(cfg):
    CALLS.append("rehearsal_config")
    return gqa.rehearsal_config(cfg)


def block_parts(cfg):
    CALLS.append("block_parts")
    return gqa.block_parts(cfg)


def prefill_flops(cfg, prompt, reused):
    CALLS.append("prefill_flops")
    return FLOPS_PER_CALL


def scope_costs(cfg, prompt, reused):
    CALLS.append("scope_costs")
    return {"attention": SCOPE_COST}


def init_weights(cfg, seed):
    CALLS.append("init_weights")
    return gqa.init_weights(cfg, seed)


def forward(cfg, w, tokens, head_rows=None, fp8=False):
    CALLS.append("forward")
    rows, logits = gqa.forward(cfg, w, tokens, head_rows, fp8)
    return rows * ROW_SCALE, logits
