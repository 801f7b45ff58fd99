"""Table 1 analogue: per-operator resource budget.

FPGA LUT/BRAM%% -> TPU resource budget: VMEM working set claimed by each
kernel's BlockSpecs (vs 128 MiB/core on v5e... we report vs 16 MiB
VMEM-per-core class budget), plus flops/bytes per call from the jnp
reference (exact op counts)."""
from __future__ import annotations


from benchmarks.common import row

VMEM_BYTES = 16 * 2**20        # v5e-class per-core VMEM


def _vmem(*shapes_dtypes) -> int:
    total = 0
    for shape, bts in shapes_dtypes:
        n = 1
        for d in shape:
            n *= d
        total += n * bts
    return total


def run() -> None:
    # select_project: column-major (C=8, 256) f32 block in + out, 3
    # (8, 1) param columns (one (8, 128) tile each), the triangular and
    # permutation matrices, and the two 16-bit halves moved through them
    br, c = 256, 8
    v = _vmem(((c, br), 4), ((c, br), 4), ((3 * c, 128), 4),
              ((br, br), 4), ((br, br), 4), ((2 * c, br), 4))
    row("resources", "select_project", 0, vmem_kb=v // 1024,
        vmem_pct=round(100 * v / VMEM_BYTES, 2),
        flops_per_row=2 * c + 4 * c * br)  # predicate + two perm matmuls

    # hash_group: (1, 256) bucket/owns rows, (V=8, 256) values, the
    # (1, B) count and (V, B) sum/min/max tables (B=1024), the one-hot
    b, vcols = 1024, 8
    v = _vmem(((8, br), 4), ((8, br), 4), ((vcols, br), 4), ((8, b), 4),
              ((vcols, b), 4), ((vcols, b), 4), ((vcols, b), 4),
              ((b, br), 4))
    row("resources", "hash_group", 0, vmem_kb=v // 1024,
        vmem_pct=round(100 * v / VMEM_BYTES, 2),
        flops_per_row=2 * b * (2 + vcols))

    # dfa_match: chars (L=64,128) + table (256,S=32) + state one-hots
    l, nstr, s = 64, 128, 32
    v = _vmem(((l, nstr), 4), ((256, s), 4), ((s, nstr), 4),
              ((256, nstr), 4))
    row("resources", "dfa_match", 0, vmem_kb=v // 1024,
        vmem_pct=round(100 * v / VMEM_BYTES, 2),
        flops_per_char=2 * s * 256)

    # ctr_crypt: (256,128) u32 in/out + keystream
    v = _vmem(((256, 128), 4), ((256, 128), 4), ((256, 128), 4))
    row("resources", "ctr_crypt", 0, vmem_kb=v // 1024,
        vmem_pct=round(100 * v / VMEM_BYTES, 2),
        flops_per_word=5 * 20)            # ~5 ops x 20 rounds

    # decode_attention: q (8,128) + kv blocks (256,128)x2 + acc
    g, d, bkv = 8, 128, 256
    v = _vmem(((g, d), 4), ((bkv, d), 4), ((bkv, d), 4), ((g, bkv), 4),
              ((g, d), 4))
    row("resources", "decode_attention", 0, vmem_kb=v // 1024,
        vmem_pct=round(100 * v / VMEM_BYTES, 2),
        flops_per_kv_row=4 * g * d)
