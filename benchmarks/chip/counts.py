"""Operations and bytes that a kernel call needs, from its shapes: the
numerators of the roofline shares. (A model's FLOPs per token, the
numerator of its FLOP utilization, is its reference module's count.)

Each function counts what the algorithm has to do for the work the call
serves, whatever kernel implements it: the valid tokens of the rows in
use, in the mode their pages are stored in, and the weights once.
"""
from __future__ import annotations

import json
import os
from typing import Iterable

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks (peaks.json); an unknown kind is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def kv_token_bytes(head_dim: int, mode: str) -> int:
    """Bytes of one token of one KV head, K and V together, in a page of
    `mode`: "normal" stores bf16 rows; "int4" / "int8" store the packed
    row and one bf16 scale per row."""
    if mode == "normal":
        return 2 * head_dim * 2
    bits = {"int4": 4, "int8": 8}[mode]
    return 2 * (head_dim * bits // 8 + 2)


def paged_attention(lengths: Iterable[tuple[int, int]], *, n_heads: int,
                    n_kv_heads: int, head_dim: int,
                    aug_mode: str = "int4") -> tuple[int, int]:
    """(FLOPs, bytes) of one paged decode-attention call (one layer).

    `lengths` holds one (normal_tokens, augmented_tokens) pair per row in
    use: the row's valid tokens split by the mode of the page that holds
    each. Bytes: every valid token's K and V in its stored form, plus the
    row's query and output (bf16). FLOPs: the two products q.K and p.V,
    2 * head_dim each per token and query head."""
    flops = nbytes = 0
    for n_normal, n_aug in lengths:
        tokens = n_normal + n_aug
        flops += 4 * n_heads * head_dim * tokens
        nbytes += n_kv_heads * (n_normal * kv_token_bytes(head_dim, "normal")
                                + n_aug * kv_token_bytes(head_dim, aug_mode))
        nbytes += 2 * n_heads * head_dim * 2
    return flops, nbytes


def dual_plane_matmul(m: int, k: int, n: int) -> tuple[int, int]:
    """(FLOPs, bytes) of x (m, k) bf16 against both int4 planes of one
    (k, n) uint8 buffer: the packed buffer once, two f32 scale rows, x,
    and the two bf16 outputs."""
    flops = 2 * m * k * 2 * n
    nbytes = k * n + 2 * n * 4 + m * k * 2 + 2 * m * n * 2
    return flops, nbytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> tuple[float, str]:
    """(share in %, bound) of a kernel that took `seconds` of device time
    for `flops` and `nbytes`: the least time the chip could take, the
    larger of the compute and the memory time, over the time taken."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    bound = "memory" if t_memory >= t_compute else "compute"
    return 100.0 * max(t_compute, t_memory) / seconds, bound
