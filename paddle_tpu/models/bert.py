"""BERT family (reference: BERT-base pretraining, which in the reference
exercises the fused_attention/fused_feedforward PHI kernels —
here the equivalent fusion happens inside nn.TransformerEncoder, whose
attention rides the registry scaled_dot_product_attention (Pallas flash
kernel on TPU) and whose LN/FFN chains XLA fuses; the standalone
incubate fused_attention/fused_feedforward ops cover API parity
separately. Pretraining heads: masked-LM + next-sentence.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
from ..enforce import OutOfRangeError, enforce

from .. import nn
from ..nn import functional as F

__all__ = ["BertConfig", "BertModel", "BertForPretraining", "bert_base",
           "bert_large", "bert_pretrain_loss", "pack_sequences"]


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout: float = 0.1
    layer_norm_eps: float = 1e-12


def bert_base(**kw):
    return BertConfig(**kw)


def bert_large(**kw):
    return BertConfig(hidden_size=1024, num_layers=24, num_heads=16,
                      intermediate_size=4096, **kw)


class BertEmbeddings(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.word_embeddings = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.position_embeddings = nn.Embedding(cfg.max_position_embeddings,
                                                cfg.hidden_size)
        self.token_type_embeddings = nn.Embedding(cfg.type_vocab_size,
                                                  cfg.hidden_size)
        self.layer_norm = nn.LayerNorm(cfg.hidden_size,
                                       epsilon=cfg.layer_norm_eps)
        self.dropout = nn.Dropout(cfg.hidden_dropout)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        B, S = input_ids.shape
        pos = (jnp.arange(S)[None, :] if position_ids is None
               else position_ids)
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        x = (self.word_embeddings(input_ids)
             + self.position_embeddings(pos)
             + self.token_type_embeddings(token_type_ids))
        return self.dropout(self.layer_norm(x))


class BertModel(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = BertEmbeddings(cfg)
        enc_layer = nn.TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
            dropout=cfg.hidden_dropout, activation="gelu",
            normalize_before=False)
        self.encoder = nn.TransformerEncoder(enc_layer, cfg.num_layers)
        self.pooler = nn.Linear(cfg.hidden_size, cfg.hidden_size)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                pack_segment_ids=None, position_ids=None):
        """pack_segment_ids: int32 [B, S] ids of PACKED sequences sharing a
        row (zero-padding-free pretraining — the reference's flash varlen
        path, flash_attention.py:242 cu_seqlens form). Distinct from BERT's
        token_type_ids ("segment A/B" within ONE sequence). When packing,
        pass position_ids that restart at each sequence start so learned
        position embeddings match the unpacked layout.

        PAD-POSITION semantics: a 2-D padding attention_mask is rewritten
        as segment ids (below), under which pad QUERY positions attend
        only to other pads — with the additive-mask form they attended to
        all valid tokens. Loss, pooled output and every valid position are
        unaffected (pads are masked out of the loss and valid queries
        never look at pads either way); only callers that read hidden
        states AT pad positions see different values, and those values
        were never meaningful."""
        x = self.embeddings(input_ids, token_type_ids, position_ids)
        if (attention_mask is not None and attention_mask.ndim == 2
                and pack_segment_ids is None):
            # [B, S] padding mask == packing with ONE segment: express it
            # as segment ids (valid -> 0, pad -> -1) so the attention
            # kernel compares int ids per tile instead of loading an
            # additive [bq, bk] fp32 mask — the padded path rides the
            # packed infrastructure. Valid tokens never attend pads
            # (0 != -1); pad rows are ignored by the loss either way.
            pack_segment_ids = jnp.where(attention_mask > 0, 0, -1) \
                .astype(jnp.int32)
            attention_mask = None
        elif attention_mask is not None and attention_mask.ndim == 2:
            # [B, S] padding mask → additive [B, 1, 1, S]
            attention_mask = jnp.where(
                attention_mask[:, None, None, :] > 0, 0.0, -1e30)
        seq = self.encoder(x, src_mask=attention_mask,
                           segment_ids=pack_segment_ids)
        pooled = jnp.tanh(self.pooler(seq[:, 0]))
        return seq, pooled


class BertForPretraining(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.bert = BertModel(cfg)
        self.mlm_transform = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.mlm_norm = nn.LayerNorm(cfg.hidden_size,
                                     epsilon=cfg.layer_norm_eps)
        self.mlm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size)
        self.nsp_head = nn.Linear(cfg.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                pack_segment_ids=None, position_ids=None):
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask,
                                pack_segment_ids=pack_segment_ids,
                                position_ids=position_ids)
        h = self.mlm_norm(F.gelu(self.mlm_transform(seq), approximate=True))
        return self.mlm_head(h), self.nsp_head(pooled)


def pack_sequences(seqs, seq_len: int, pad_id: int = 0):
    """Greedy first-fit packing of variable-length token sequences into
    dense [rows, seq_len] batches with NO cross-sequence attention: returns
    (input_ids, pack_segment_ids, position_ids, row_of_seq, offset_of_seq).

    pack_segment_ids gives every sequence a distinct id within its row (pad
    tail = -1 so it matches nothing); position_ids restart at 0 per
    sequence. This is the zero-padding path the reference serves through
    flash_attn varlen/cu_seqlens (python/paddle/nn/functional/
    flash_attention.py:242); here the ids ride the Pallas kernel's
    in-kernel segment masking."""
    import numpy as np

    rows, row_lens = [], []
    row_of_seq, offset_of_seq = [], []
    for s in seqs:
        L = len(s)
        enforce(L <= seq_len,
                f"sequence of {L} tokens exceeds row {seq_len}",
                op="bert.pack_sequences", error=OutOfRangeError)
        for r in range(len(rows)):
            if row_lens[r] + L <= seq_len:
                break
        else:
            rows.append([])
            row_lens.append(0)
            r = len(rows) - 1
        row_of_seq.append(r)
        offset_of_seq.append(row_lens[r])
        rows[r].append(np.asarray(s))
        row_lens[r] += L

    B = len(rows)
    ids = np.full((B, seq_len), pad_id, dtype=np.int32)
    seg = np.full((B, seq_len), -1, dtype=np.int32)
    pos = np.zeros((B, seq_len), dtype=np.int32)
    for r, chunks in enumerate(rows):
        off = 0
        for i, c in enumerate(chunks):
            ids[r, off:off + len(c)] = c
            seg[r, off:off + len(c)] = i
            pos[r, off:off + len(c)] = np.arange(len(c))
            off += len(c)
    return ids, seg, pos, row_of_seq, offset_of_seq


def bert_pretrain_loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels,
                       ignore_index: int = -100):
    """MLM CE over masked positions + NSP CE (reference pretrain loss) —
    both terms ride the framework's cross_entropy (one implementation of
    the masked-CE numerics)."""
    mlm = F.cross_entropy(mlm_logits, mlm_labels,
                          ignore_index=ignore_index, reduction="mean")
    nsp = F.cross_entropy(nsp_logits, nsp_labels, reduction="mean")
    return mlm + nsp
