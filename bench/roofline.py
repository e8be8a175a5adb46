"""Peaks of the chip and the operations and bytes the algorithm needs.

Counts are of what the algorithm needs, from the shapes and context
lengths a window ran: live rows only, each row's planned draft length (not
the dispatched window), true prompt lengths (not their power-of-two
buckets), and context tokens (not the unused tails of cache pages).  A
later change that stops computing padding therefore cannot lift a share
past 100%.  What a token or a row of attention costs is the model's
architecture's to say (``arch.of(section).token_flops`` and
``.attention``); the draft and the target each count through their own.
"""

from __future__ import annotations

import arch

# Published peaks per chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2 at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add its "
                       "published numbers to bench/roofline.py")
    return PEAKS[device_kind]


def tree_nodes(tokens, Lk: int) -> int:
    """Distinct draft prefixes of one row's (J, L) forest: the nodes of
    its prefix-deduplicated token tree."""
    return len({tuple(int(t) for t in run[:l]) for run in tokens
                for l in range(1, Lk + 1)})


def round_work(config: dict, rnd) -> dict:
    """What one recorded round needs over its live rows: model FLOPs of
    the draft and target passes, and the attention FLOPs and bytes of the
    draft passes and of the target window apart."""
    t, dm = config["target"], config["draft"]
    at, ad = arch.of(t), arch.of(dm)
    J = rnd.J
    out = dict(flops=0.0, draft_attn=[0.0, 0.0], target_attn=[0.0, 0.0],
               verify_positions=0)
    for b, (_, i0) in rnd.live.items():
        Lk = int(rnd.lengths[b])
        # draft: J runs of Lk + 1 single-token passes (the last writes d_L)
        for s in range(Lk + 1):
            f, by = ad.attention(dm, i0 + s, 1)
            out["flops"] += J * (ad.token_flops(dm) + f)
            out["draft_attn"][0] += J * f
            out["draft_attn"][1] += J * by
        # target: one window over pending and the row's draft tokens
        T = 1 + (Lk if J == 1 else tree_nodes(rnd.out["tokens"][b], Lk))
        f, by = at.attention(t, i0, T)
        out["flops"] += T * at.token_flops(t) + f
        out["target_attn"][0] += f
        out["target_attn"][1] += by
        out["verify_positions"] += Lk + 1
    return out


def prefill_work(config: dict, M: int) -> dict:
    """A prompt of M tokens: both models prefill its first M - 1.  Model
    FLOPs of both, and each model's attention FLOPs and bytes apart."""
    out = {"flops": 0.0}
    for role in ("target", "draft"):
        m = config[role]
        a = arch.of(m)
        f, by = a.attention(m, 0, M - 1)
        out["flops"] += (M - 1) * a.token_flops(m) + f
        out[f"{role}_attn"] = [f, by]
    return out


def share(flops: float, nbytes: float, seconds: float, pk: dict):
    """Least time over measured time, and which bound sets the least."""
    t_c = flops / pk["bf16_flops_s"]
    t_m = nbytes / pk["hbm_bytes_s"]
    bound = "compute" if t_c >= t_m else "memory"
    return max(t_c, t_m) / seconds, bound
