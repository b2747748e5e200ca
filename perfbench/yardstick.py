"""The benchmark's yardstick: chip peaks and the operations and bytes the
algorithms need, computed from shapes. Copied here from the program
(``repro.launch.roofline``) so that it does not move when the program
does."""
from __future__ import annotations

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect (4 ICI links of 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9,
                    "ici_link_bw": 50e9, "ici_links": 4},
}


def chip_peaks(device_kind: str) -> dict:
    """A chip's published peaks; a kind not in ``PEAKS`` is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


def conv_flops(h: int, w: int, k: int, c_in: int, c_out: int) -> int:
    """Multiply-adds of a stride-1 SAME k×k convolution, as 2 FLOP each."""
    return 2 * h * w * k * k * c_in * c_out


def cnn_forward_flops(hw: int, channels: int, width: int,
                      classes: int) -> int:
    """Forward FLOPs per image of ``core.tasks.cnn_task``: conv 3×3 →
    pool 2 → conv 3×3 (2·width) → pool 2 → dense. Only the
    multiply-adds count (2,063,488 at 28×28×1, width 16, 10 classes)."""
    h2 = hw // 2
    flat = (hw // 4) ** 2 * 2 * width
    return (conv_flops(hw, hw, 3, channels, width)
            + conv_flops(h2, h2, 3, width, 2 * width)
            + 2 * flat * classes)


def engine_round_train_flops(*, workers: int, local_epochs: int,
                             steps_per_epoch: int, batch: int,
                             image_flops: int) -> int:
    """FLOPs one DeFTA round's local training requires: every worker's
    SGD steps, forward plus backward (3× the forward)."""
    return workers * local_epochs * steps_per_epoch * batch * 3 * image_flops


def train_flops_per_token(n_params: int) -> float:
    """6·N: forward and backward matmul FLOPs per trained token
    (attention scores and recompute not counted). 3.576e9 for
    qwen3-0.6b's 596,049,920 parameters."""
    return 6.0 * n_params


WIRE_BYTES = {"float32": 4, "fp32": 4, "bf16": 2, "bfloat16": 2, "int8": 1}


def padded_csr_mix(rows: int, slots: int, features: int, wire: str,
                   out_bytes: int = 4) -> dict:
    """Operations and bytes of one padded-CSR gossip mix of a [rows, F]
    stack with ``slots`` (max row degree, self included) weights per row:
    out[i] = Σ_k val[i, k] · x[idx[i, k]]. Each stack row is read once
    (the kernel keeps a column block of the whole stack in VMEM), each
    output row written once; idx and val are 4 B each, and an int8 wire
    adds one f32 scale per row."""
    ops = 2 * rows * slots * features
    nbytes = rows * features * (WIRE_BYTES[wire] + out_bytes) \
        + rows * slots * 8
    if WIRE_BYTES[wire] == 1:
        nbytes += 4 * rows
    return {"ops": ops, "bytes": nbytes}


def roofline_time(ops: float, nbytes: float, peaks: dict,
                  ops_peak: str = "bf16_flops"):
    """Least time on the chip and the bound that sets it."""
    t_ops, t_mem = ops / peaks[ops_peak], nbytes / peaks["hbm_bw"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
