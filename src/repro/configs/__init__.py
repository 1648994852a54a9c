"""Config registry: ``get_config("<arch-id>")`` -> ArchConfig.

One module per assigned architecture (exact dims from the assignment,
source cited in each module docstring) plus the paper's own MLP example.
"""
from __future__ import annotations

from repro.models.common import ArchConfig

from . import (dbrx_132b, gemma2_2b, granite_4_0_h_small, granite_20b,
               internvl2_76b,
               jamba_1_5_large_398b, llama4_scout_17b_a16e, mamba2_130m,
               qwen1_5_0_5b, starcoder2_15b, whisper_base)

_MODULES = {
    m.ARCH_ID: m
    for m in (dbrx_132b, internvl2_76b, qwen1_5_0_5b, gemma2_2b,
              jamba_1_5_large_398b, whisper_base, llama4_scout_17b_a16e,
              starcoder2_15b, mamba2_130m, granite_20b, granite_4_0_h_small)
}

ARCH_IDS = list(_MODULES)

# archs whose attention is sub-quadratic-capable (run long_500k natively);
# others need the sequence-sharded ring path (DESIGN.md §5, §8)
LONG_CONTEXT_ARCHS = {"mamba2-130m", "jamba-1.5-large-398b", "gemma2-2b"}


def get_config(arch_id: str, *, long_context: bool = False,
               seq_shard: bool = False) -> ArchConfig:
    """``long_context=True`` returns the arch's long-context serving
    variant.  Sub-quadratic archs (``LONG_CONTEXT_ARCHS``) have a native
    one (windowed/SSM).  Full-attention archs are only viable with the
    sequence-sharded ring attention path — pass ``seq_shard=True``
    (mirroring ``PerfFlags.seq_shard``) to acknowledge that, and the base
    config is returned unchanged: attention stays full, and the O(S·S/P)
    per-device footprint comes from ``dist/ring.py`` (DESIGN.md §8)."""
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; have {ARCH_IDS}")
    mod = _MODULES[arch_id]
    if long_context:
        if arch_id not in LONG_CONTEXT_ARCHS:
            if not seq_shard:
                raise ValueError(
                    f"{arch_id} has no sub-quadratic long-context variant; "
                    f"full-attention archs run long_500k only on the "
                    f"sequence-sharded ring path (seq_shard=True, "
                    f"DESIGN.md §8)")
            return mod.config()
        import inspect
        if "long_context" in inspect.signature(mod.config).parameters:
            return mod.config(long_context=True)
    return mod.config()
