"""Kernel micro-benchmarks: Pallas (interpret) vs jnp reference wall-time on
CPU is not meaningful for TPU perf — this benchmark instead reports the
kernels' arithmetic intensity and VMEM working set per BlockSpec tile,
the quantities that determine MXU utilization on the target."""
from __future__ import annotations

from benchmarks.common import Csv


def ffn_tile_stats(D: int, F: int, bc: int, bf: int, dtype_bytes: int = 2):
    flops = 2 * bc * D * bf * 3          # gate+up+down matmuls
    vmem = (bc * D + 2 * D * bf + bf * D + bc * D) * dtype_bytes
    hbm = (bc * D + 3 * D * bf) * dtype_bytes + bc * D * 4
    return flops, vmem, flops / hbm


def run(csv: Csv) -> dict:
    out = {}
    cases = [
        ("qwen3_expert", 4096, 1536, 128, 128),
        ("olmoe_expert", 2048, 1024, 128, 128),
        ("dsv2lite_expert", 2048, 1408, 128, 128),
        ("qwen2moe_expert", 3584, 2560, 128, 128),
        ("qwen3_expert_bigtile", 4096, 1536, 256, 256),
        ("qwen3_expert_smalltile", 4096, 1536, 64, 128),
    ]
    for name, D, F, bc, bf in cases:
        flops, vmem, ai = ffn_tile_stats(D, F, bc, bf)
        fits = vmem < 8 * 2**20   # conservative half-VMEM budget
        # MXU-bound time per tile at v5e vs HBM-bound
        t_mxu = flops / 197e12
        t_hbm = (vmem) / 819e9
        out[name] = ai
        csv.add(f"kernels/moe_gemm/{name}", t_mxu * 1e6,
                f"ai={ai:.1f}flops/B;vmem_tile={vmem/2**20:.2f}MiB;"
                f"fits_vmem={fits};mxu_bound={t_mxu > t_hbm}")
    return out


if __name__ == "__main__":
    run(Csv())
