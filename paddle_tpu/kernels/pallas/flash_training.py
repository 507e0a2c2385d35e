"""Training-grade flash attention plan for the hybrid engines.

The op-registry hop (``F.scaled_dot_product_attention`` →
``register.py`` dispatch) is the right surface for eager/nn users, but
the hybrid training hot path wants the fused kernel wired DIRECTLY into
the block bodies — no per-call ``supported()`` predicate, no
registry-flag dependence inside a compiled step, and a plan object the
builders thread exactly like ``fp8=``/``sp=`` (one resolution shared by
gpt and llama so flag semantics can never drift).

``FlashAttentionConfig`` is that plan:

* ``block_q``/``block_k`` — kernel tile sizes (0 = the kernel's own
  auto-pick, ``flash_attention._pick_block``);
* ``sep`` — optional context parallelism over a ``sep`` mesh axis, with
  the flash kernel as the per-shard inner compute:
  ``"ring"`` rotates K/V blocks over the axis
  (``context_parallel.ring_attention`` — the tiled impl runs the flash
  fwd/bwd kernels per visiting block), ``"ulysses"`` trades the sequence
  shard for a head shard with one all-to-all each way and runs the flash
  kernel on the gathered sequence. Heads stay local under TP either way:
  sep composes INSIDE the mp shard (q/k/v arrive ``[B, S_local,
  heads_local, D]``).

Flags-off (``resolve_flash_attention(None)``) leaves the model bodies on
the registry op: the same kernel on the chip wherever ``takes_pallas``
admits the shape (both training cells), the composed einsum attention
elsewhere. CPU tier-1 runs the kernels in interpreter mode
(``_common.interpret``), so the whole compose matrix is testable off-TPU.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from ...enforce import enforce

__all__ = ["FlashAttentionConfig", "FLASH_SEP_MODES", "flash_from_flags",
           "resolve_flash_attention", "attention"]

FLASH_SEP_MODES = (None, "ring", "ulysses")


@dataclasses.dataclass
class FlashAttentionConfig:
    """Resolved flash-attention plan for the hybrid engines.

    block_q/block_k: kernel tile sizes (0 = auto-pick — 1024-target
    power-of-two divisors of the sequence).
    sep: None (attention runs on this rank's full local sequence) or
    "ring"/"ulysses" context parallelism over the mesh's 'sep' axis.
    """
    block_q: int = 0
    block_k: int = 0
    sep: Optional[str] = None

    def __post_init__(self):
        enforce(self.sep in FLASH_SEP_MODES,
                f"flash sep mode must be one of {FLASH_SEP_MODES}",
                op="FlashAttentionConfig", sep=self.sep)

    def meta(self) -> dict:
        """Build metadata for the telemetry JSONL header (the mp_mode /
        moe pattern in hybrid_engine.build_train_step)."""
        return {"block_q": int(self.block_q), "block_k": int(self.block_k),
                "sep": self.sep or "none"}


def flash_from_flags() -> Optional[FlashAttentionConfig]:
    """Flag-driven opt-in: None (the registry op: its Pallas arm on the
    chip) unless FLAGS_flash_attention is set; FLAGS_flash_sep picks
    the context-parallel mode, FLAGS_flash_attn_block_q/_k the tiles."""
    from ...flags import flag
    sep = flag("flash_sep") or None
    if not flag("flash_attention"):
        enforce(sep is None,
                "FLAGS_flash_sep is set but FLAGS_flash_attention is off "
                "— the sep context-parallel mode rides the flash "
                "training path; enable both or clear FLAGS_flash_sep",
                op="flash_from_flags", flash_sep=sep)
        return None
    return FlashAttentionConfig(block_q=int(flag("flash_attn_block_q")),
                                block_k=int(flag("flash_attn_block_k")),
                                sep=sep)


def resolve_flash_attention(arg) -> Optional[FlashAttentionConfig]:
    """ONE resolution of a builder's flash_attention= argument — gpt and
    llama build_hybrid_train_step both route through here (the
    resolve_fp8_plan/resolve_mp_overlap discipline). "auto" reads the
    flags (default off); None/False disables; True enables with kernel
    defaults; a sep-mode string ("ring"/"ulysses") enables with that
    context-parallel mode; a FlashAttentionConfig forces."""
    if arg == "auto":
        return flash_from_flags()
    if arg is None or arg is False:
        return None
    if arg is True:
        return FlashAttentionConfig()
    if isinstance(arg, str):
        return FlashAttentionConfig(sep=arg)
    return arg


def _kernel(q, k, v, causal, cfg: FlashAttentionConfig):
    """The fused kernel on [B, S, h, D] inputs (full sequence, local
    heads). Shape gates mirror flash_attention.supported for the shapes
    the training path can produce: Mosaic's lane tiling wants 128-multiple
    sequences on a real TPU (interpreter mode takes any power-of-two
    block), and head_dim caps at 256."""
    from . import flash_attention as fa
    from ._common import interpret as _interpret
    enforce(q.shape[-1] <= 256,
            "the flash kernel caps head_dim at 256",
            op="flash_training", head_dim=int(q.shape[-1]))
    enforce(_interpret() or (q.shape[1] % 128 == 0
                             and k.shape[1] % 128 == 0),
            "the flash kernel tiles 128-lane sequence blocks on TPU — "
            "pad the sequence to a 128 multiple upstream",
            op="flash_training", sq=int(q.shape[1]), sk=int(k.shape[1]))
    return fa.flash_attention(q, k, v, causal, None,
                              cfg.block_q or None, cfg.block_k or None)


def attention(q, k, v, cfg: FlashAttentionConfig, *, causal: bool = True,
              sep_axis: Optional[str] = None):
    """Training attention under a resolved plan. q: [B, S, h, D];
    k/v: [B, S, h_kv, D] with h % h_kv == 0 (GQA native — the kernel
    indexes KV heads per query group). Under sep, S is this rank's
    sequence shard and the call must run inside shard_map over a mesh
    that defines ``sep_axis``; global sequence order is the rank
    concatenation and causal masking uses global positions
    (context_parallel semantics)."""
    if cfg.sep is None:
        return _kernel(q, k, v, causal, cfg)
    enforce(sep_axis is not None,
            "a sep-mode flash plan needs the mesh's context-parallel axis "
            "name", op="flash_training", sep=cfg.sep)
    from ...distributed.fleet.meta_parallel.context_parallel import (
        ring_attention, ulysses_attention)
    if cfg.sep == "ring":
        # tiled impl FORCED (impl="auto" would silently drop to the
        # composed einsum ring on shapes the kernel can't take — the
        # same loud-gate contract as _kernel): the flash fwd/bwd kernels
        # run per visiting K/V block with the global logsumexp
        # (hand-written reverse ring). The ring picks its own per-shard
        # tiles (_pick_block); cfg.block_q/block_k apply to the
        # non-sep/ulysses kernel calls only.
        from ._common import interpret as _interpret
        enforce(q.shape[-1] <= 256,
                "the flash kernel caps head_dim at 256",
                op="flash_training", head_dim=int(q.shape[-1]))
        enforce(_interpret() or q.shape[1] % 128 == 0,
                "ring flash tiles 128-lane sequence shards on TPU — "
                "pad so S/sep is a 128 multiple",
                op="flash_training", s_local=int(q.shape[1]))
        return ring_attention(q, k, v, axis=sep_axis, causal=causal,
                              impl="tiled")
    # ulysses: all-to-all to a head shard, flash on the full sequence,
    # all-to-all back — flash IS the per-shard inner kernel
    return ulysses_attention(
        q, k, v, axis=sep_axis, causal=causal,
        attn_fn=lambda qh, kh, vh, c: _kernel(qh, kh, vh, c, cfg))
