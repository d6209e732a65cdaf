"""Operations and bytes from shapes: the benchmark's yardstick.

Every share of a peak that a per-layer metric reports divides a device
time into a number from this file.  Nothing here asks the program: the
inputs are a configuration file's published sizes (Hugging Face key
names) and the shapes a cell states.  Conventions, fixed here so that a
later PR cannot move them:

- a multiply-add is 2 operations;
- attention is counted causal: a query at absolute position p meets
  p + 1 keys, and the two matmuls (scores, values) cost
  4 * heads * head_dim operations per query-key pair;
- the embedding table is a gather, not a matmul: it is in no count of
  operations; the output head is;
- training is forward + backward = 3 x forward for matmuls and for
  attention; recomputation (remat, the flash backward's second pass over
  the scores) is NOT in a model count (`train_step_flops`), and IS in a
  kernel's own count (`flash_bwd_dq_flops`, `flash_bwd_dkv_flops`), because a kernel's roofline is
  about the work that kernel's algorithm does.
"""

from __future__ import annotations

from typing import Iterable, Mapping

BF16 = 2
F32 = 4


def head_dim(c: Mapping) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def layer_matmul_params(c: Mapping) -> int:
    """wq, wk, wv, wo, gate, up, down of one block."""
    d, hd = c["hidden_size"], head_dim(c)
    q = d * c["num_attention_heads"] * hd
    kv = 2 * d * c["num_key_value_heads"] * hd
    o = c["num_attention_heads"] * hd * d
    ffn = 3 * d * c["intermediate_size"]
    return q + kv + o + ffn


def head_params(c: Mapping) -> int:
    return c["hidden_size"] * c["vocab_size"]


def embed_params(c: Mapping) -> int:
    return c["hidden_size"] * c["vocab_size"]


def norm_params(c: Mapping) -> int:
    return (2 * c["num_hidden_layers"] + 1) * c["hidden_size"]


def total_params(c: Mapping) -> int:
    out_head = 0 if c.get("tie_word_embeddings") else head_params(c)
    return (embed_params(c) + c["num_hidden_layers"] * layer_matmul_params(c)
            + norm_params(c) + out_head)


def matmul_params(c: Mapping) -> int:
    """Parameters that a token is multiplied by: blocks + output head,
    no embedding table, no norm vectors."""
    return c["num_hidden_layers"] * layer_matmul_params(c) + head_params(c)


def weight_bytes(c: Mapping, bytes_per_param: int = BF16) -> int:
    return total_params(c) * bytes_per_param


def kv_bytes_per_token(c: Mapping, bytes_per_value: int = BF16) -> int:
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * head_dim(c) * bytes_per_value)


def attn_pair_flops(c: Mapping) -> int:
    """Operations one query spends on one key in one layer."""
    return 4 * c["num_attention_heads"] * head_dim(c)


# ------------------------------------------------------------------ serve

def decode_step_bytes(c: Mapping, live_positions: Iterable[int]) -> float:
    """Bytes one decode step has to read: every block's weights, the
    norms and the head once (bf16), one embedding row per live
    sequence, and the KV rows that are LIVE (positions 0..pos of each
    live sequence), not the rows the table could hold."""
    live = list(live_positions)
    w = (c["num_hidden_layers"] * layer_matmul_params(c) + head_params(c)
         + norm_params(c)) * BF16
    emb = len(live) * c["hidden_size"] * BF16
    kv = sum(p + 1 for p in live) * kv_bytes_per_token(c)
    return float(w + emb + kv)


# ------------------------------------------------------------------ train

def train_step_flops(c: Mapping, sequences: int, positions: int) -> float:
    """Model operations one optimizer step needs: forward + backward
    over `sequences` x `positions` tokens; no recomputation."""
    tokens = sequences * positions
    mat = 6.0 * tokens * matmul_params(c)
    pairs = sequences * positions * (positions + 1) / 2.0
    attn = 3.0 * c["num_hidden_layers"] * attn_pair_flops(c) * pairs
    return mat + attn


def train_flops_per_token(c: Mapping, positions: int) -> float:
    return train_step_flops(c, 1, positions) / positions


def flash_fwd_flops(heads: int, hd: int, sequences: int, positions: int) -> float:
    """One forward flash call: scores and values, causal."""
    return 4.0 * heads * hd * sequences * positions * (positions + 1) / 2.0


def flash_bwd_dq_flops(heads: int, hd: int, sequences: int, positions: int) -> float:
    """The program's backward is two kernels, and each needs the scores
    and dP again (the forward does two matmuls over the causal pairs):
    the dQ kernel does three (scores, dP, dQ)."""
    return 1.5 * flash_fwd_flops(heads, hd, sequences, positions)


def flash_bwd_dkv_flops(heads: int, hd: int, sequences: int, positions: int) -> float:
    """The dK/dV kernel does four (scores, dP, dV, dK)."""
    return 2.0 * flash_fwd_flops(heads, hd, sequences, positions)


def flash_fwd_bytes(heads: int, hd: int, sequences: int, positions: int,
                    bytes_per_value: int = BF16) -> float:
    """q, k, v read and o written once (k and v already repeated to
    `heads`, as the program hands them to the kernel), plus the f32
    log-sum-exp row."""
    t = sequences * positions * heads
    return 4.0 * t * hd * bytes_per_value + t * F32


def flash_bwd_bytes(heads: int, hd: int, sequences: int, positions: int,
                    bytes_per_value: int = BF16) -> float:
    """q, k, v, o, do read; dq, dk, dv written; lse and delta rows."""
    t = sequences * positions * heads
    return 8.0 * t * hd * bytes_per_value + 2 * t * F32


def least_time(flops: float, nbytes: float, peaks: Mapping) -> tuple:
    """(seconds, which bound) for one call on one chip."""
    tf = flops / peaks["bf16_flops_per_s"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")


def constants(c: Mapping) -> dict:
    """What a configuration file carries beside its sizes."""
    return {
        "layer_matmul_params": layer_matmul_params(c),
        "matmul_params": matmul_params(c),
        "total_params": total_params(c),
        "weight_bytes_bf16": weight_bytes(c, BF16),
        "kv_bytes_per_token_bf16": kv_bytes_per_token(c),
        "decode_flops_per_token_no_attention": 2 * matmul_params(c),
        "train_flops_per_token_at_4096": train_flops_per_token(c, 4096),
    }
