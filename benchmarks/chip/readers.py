"""Shared arithmetic of the per-layer metric readers (metrics/*.py).

A reader takes the run's `harness.Context` and returns one number, or None
when the run holds nothing for it to read; it never returns 0 for a share
of a roofline or of a peak that it could not measure.
"""
from __future__ import annotations

import numpy as np

import counts


def idle_share(ctx) -> float | None:
    """% of the traced window in which no operation ran on the device."""
    t = ctx.trace
    if t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def host_ms_per_step(ctx) -> float | None:
    """Mean host time of a `step_all` call: its span's wall time less the
    device-busy time inside it."""
    spans = [e for e in ctx.trace.host if e.name == "bench.step_all"
             and e.start >= ctx.trace.window[0]
             and e.end <= ctx.trace.window[1]]
    if not spans:
        return None
    host = sum((e.end - e.start) - ctx.trace.overlap(e.start, e.end)
               for e in spans)
    return 1e3 * host / len(spans)


def queue_wait_ms(ctx) -> float | None:
    """Mean time from due to admitted into a row, over the requests due in
    the window; one still queued at the close counts the wait so far."""
    c = ctx.client
    waits = [c.admitted.get(r, ctx.t_close) - d for r, d in c.due.items()
             if ctx.t0 <= d < ctx.t_close]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)


def window_percentile(ctx, key: str, q: float) -> float | None:
    """The q-th percentile, in ms, of the window's `key` samples ("ttft"
    or "itl", harness.window_numbers), over every sample."""
    values = ctx.window[key]
    if not values:
        return None
    return 1e3 * float(np.percentile(np.asarray(values, float), q))


def program_ms(ctx, which: str) -> float | None:
    """Mean device time of one run of the engine's jitted `which`
    ("decode" or "prefill") program in the window."""
    runs = ctx.trace.program_runs(which)
    if not runs:
        return None
    return 1e3 * sum(e.end - e.start for e in runs) / len(runs)


def decode_tokens(ctx) -> list[int]:
    """Attended context of every token served by a step in the window."""
    return [n for s in ctx.steps_in_window() for n in s.contexts]


def step_mfu(ctx) -> float | None:
    """Model FLOPs of the tokens served in the window (the configuration's
    reference counts them), per second of the window, as a % of the
    chip's bf16 peak."""
    toks = decode_tokens(ctx)
    secs = ctx.t_close - ctx.t0
    if not toks or secs <= 0:
        return None
    flops = sum(ctx.reference.decode_token_flops(ctx.dims, n) for n in toks)
    return 100.0 * flops / secs / ctx.peaks["bf16_flops_per_s"]


def paged_attention_roofline(ctx) -> float | None:
    """Share of its roofline that the paged decode-attention kernel
    reached in the window: the least time its calls need (counts.py:
    every served row's valid tokens as stored, q and out, per layer)
    over the kernel's summed device time."""
    secs = ctx.trace.kernel_seconds("paged_kv_attention", within="decode")
    steps = ctx.steps_in_window()
    if secs <= 0 or not steps:
        return None
    if ctx.cell.config["amc"]["pool_mode"] != "always-augmented":
        return None          # the modes of the pages are not recorded
    m = ctx.dims
    mode = ctx.cell.config["amc"]["kv_mode"]
    flops = nbytes = 0
    for s in steps:
        f, b = counts.paged_attention(
            [(0, n) for n in s.contexts], n_heads=m.n_heads,
            n_kv_heads=m.n_kv_heads, head_dim=m.head_dim, aug_mode=mode)
        flops += f * m.n_layers
        nbytes += b * m.n_layers
    share, bound = counts.roofline_share(flops, nbytes, secs, ctx.peaks)
    ctx.note(f"paged_kv_attention: {flops} FLOPs, {nbytes} B in "
             f"{secs!r} s of kernel time, {bound}-bound")
    return share


def dual_matmul_roofline(ctx) -> float | None:
    """Share of its roofline that the dual-plane matmul reached inside
    the decode program's runs: per layer and step one call on wk+wv and
    one on w_gate+w_up, over the rows served."""
    secs = ctx.trace.kernel_seconds("dual_plane_matmul", within="decode")
    steps = ctx.steps_in_window()
    if secs <= 0 or not steps:
        return None
    m = ctx.dims
    flops = nbytes = 0
    for s in steps:
        rows = len(s.contexts)
        for n in (m.n_kv_heads * m.head_dim, m.d_ff):
            f, b = counts.dual_plane_matmul(rows, m.d_model, n)
            flops += f * m.n_layers
            nbytes += b * m.n_layers
    share, bound = counts.roofline_share(flops, nbytes, secs, ctx.peaks)
    ctx.note(f"dual_plane_matmul: {flops} FLOPs, {nbytes} B in "
             f"{secs!r} s of kernel time, {bound}-bound")
    return share
