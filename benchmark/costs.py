"""Operations and bytes of a decode step and of a prefill, from a
configuration's shapes: the arithmetic the predictions in PERF.md use now
and roofline metrics will use once kernels carry names (the ``tracing``
issue). Matmul FLOPs are 2·m·n·k; attention FLOPs 4·tokens·context·heads·d
(QKᵀ and PV). Bytes are what the algorithm has to move: every weight once
per program, the KV it reads, and nothing for activations that could stay
on chip.

``experts="dense"`` counts what the program does today (every expert
computed for every token: ``run_experts_dense``); ``experts="routed"``
counts what the model needs (top-k experts per token). Their ratio is the
room ROADMAP S2 has in a compute-bound prefill.
"""

from __future__ import annotations


def shapes(hf: dict) -> dict:
    heads = int(hf["num_attention_heads"])
    hidden = int(hf["hidden_size"])
    experts = int(hf.get("num_experts") or hf.get("num_local_experts") or 0)
    return {
        "L": int(hf["num_hidden_layers"]), "D": hidden, "H": heads,
        "KVH": int(hf.get("num_key_value_heads", heads)),
        "d": int(hf.get("head_dim") or hidden // heads),
        "V": int(hf["vocab_size"]),
        "F": int(hf["moe_intermediate_size"] if experts
                 else hf["intermediate_size"]),
        "E": experts, "K": int(hf.get("num_experts_per_tok") or 0),
        "Fs": int(hf.get("shared_expert_intermediate_size") or 0),
        "tied": bool(hf.get("tie_word_embeddings", False)),
    }


def layer_params(s: dict) -> dict:
    """Weight elements of one layer, by part."""
    attn = s["D"] * (s["H"] + 2 * s["KVH"]) * s["d"] + s["H"] * s["d"] * s["D"]
    if s["E"]:
        mlp = s["E"] * 3 * s["D"] * s["F"] + s["D"] * s["E"]
        shared = 3 * s["D"] * s["Fs"] + (s["D"] if s["Fs"] else 0)
    else:
        mlp, shared = 3 * s["D"] * s["F"], 0
    return {"attn": attn, "mlp": mlp, "shared": shared}


def weight_bytes(hf: dict, bytes_per_weight: float = 1.0) -> float:
    """All weights, read once by a program (int8: one byte each)."""
    s = shapes(hf)
    per_layer = sum(layer_params(s).values())
    head = s["V"] * s["D"] * (1 if s["tied"] else 2)
    return (s["L"] * per_layer + head) * bytes_per_weight


def kv_bytes_per_token(hf: dict, bytes_per_value: float = 2.0) -> float:
    s = shapes(hf)
    return 2 * s["L"] * s["KVH"] * s["d"] * bytes_per_value


def forward_flops(hf: dict, tokens: int, context_tokens: float,
                  experts: str = "dense", logits_rows: int = None) -> float:
    """FLOPs of one forward over ``tokens`` rows; ``context_tokens`` is the
    sum over rows of the keys each attends to."""
    s = shapes(hf)
    p = layer_params(s)
    mlp = p["mlp"]
    if s["E"] and experts == "routed":
        mlp = s["K"] * 3 * s["D"] * s["F"] + s["D"] * s["E"]
    per_token = 2 * (p["attn"] + mlp + p["shared"])
    attn = 4 * context_tokens * s["H"] * s["d"]
    rows = tokens if logits_rows is None else logits_rows
    return s["L"] * (tokens * per_token + attn) + 2 * rows * s["D"] * s["V"]


def prefill(hf: dict, prompt_tokens: int, experts: str = "dense",
            bytes_per_weight: float = 1.0) -> dict:
    """One prompt prefilled alone (causal: row i attends to i+1 keys); the
    head runs on the last row only."""
    ctx = prompt_tokens * (prompt_tokens + 1) / 2
    return {"flops": forward_flops(hf, prompt_tokens, ctx, experts,
                                   logits_rows=1),
            "bytes": weight_bytes(hf, bytes_per_weight)
            + prompt_tokens * kv_bytes_per_token(hf)}


def decode_step(hf: dict, batch: int, mean_context: float,
                experts: str = "dense", bytes_per_weight: float = 1.0) -> dict:
    """One token for each of ``batch`` sequences."""
    ctx = batch * mean_context
    return {"flops": forward_flops(hf, batch, ctx, experts),
            "bytes": weight_bytes(hf, bytes_per_weight)
            + ctx * kv_bytes_per_token(hf)}
