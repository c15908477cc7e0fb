"""Batched serving engine: prefill + decode with continuous-batch shaping.

Batch formation uses the paper's technique: requests are **sorted by
prompt length** with the framework's sort primitive — now routed through
``repro.core.engine.SortEngine.sort_pairs`` (one multi-operand XLA sort
behind a power-of-two shape-bucketed jit cache, DESIGN.md §4), so each
padded prefill batch wastes the minimum number of pad tokens — the
serving-side face of the Array Division Procedure (DESIGN.md §3) — and a
stream of varying batch sizes reuses a handful of compiled executables
instead of recompiling per size.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import partition
from repro.core.engine import SortEngine
from repro.models.common import AxisRules, NO_SHARD


@dataclasses.dataclass
class Request:
    id: int
    prompt: np.ndarray  # (len,) int32 token ids
    max_new_tokens: int = 16


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, model_api, *, rules: AxisRules = NO_SHARD,
                 max_len: int = 512, sorter: SortEngine | None = None):
        self.cfg, self.params, self.api = cfg, params, model_api
        self.rules = rules
        self.max_len = max_len
        self.sorter = sorter if sorter is not None else SortEngine()
        self._prefill = jax.jit(
            lambda p, b, c: model_api.prefill(p, b, cfg, rules, c)
        )
        self._decode = jax.jit(
            lambda p, t, c, pos: model_api.decode_step(p, t, cfg, rules, c, pos)
        )

    # ------------------------------------------------------- batch formation
    def order_by_length(self, requests: list[Request]) -> list[Request]:
        """Sort requests by prompt length via the engine's warm pair-sort path.

        One device call and one host transfer per batch (the permutation must
        come back to reorder a Python list); the sorted *payloads* of the
        segmented batch path stay on device (``SortEngine.sort_segments`` with
        ``return_padded=True``, DESIGN.md §8) — only this index sort syncs.
        """
        if len(requests) <= 1:
            return list(requests)
        lens = jnp.asarray([len(r.prompt) for r in requests], jnp.int32)
        idx = jnp.arange(len(requests), dtype=jnp.int32)
        _, order = self.sorter.sort_pairs(lens, idx)
        return [requests[int(i)] for i in np.asarray(order)]

    def _pad_batch(self, requests: list[Request]):
        lens = [len(r.prompt) for r in requests]
        L = max(lens)
        # left-pad → aligned ends (right-aligned content): one vectorized
        # pack instead of a per-request copy loop
        toks = partition.pack_segments(
            np.concatenate([r.prompt for r in requests]) if requests else
            np.zeros(0, np.int32),
            lens, L, fill_value=0, align="right",
        ).astype(np.int32)
        return jnp.asarray(toks), L

    # --------------------------------------------------------------- serving
    def generate(self, requests: list[Request], greedy: bool = True) -> dict[int, list[int]]:
        if not requests:
            # _pad_batch's max() over an empty sequence raised a bare
            # ValueError here; an empty batch is simply an empty result.
            return {}
        requests = self.order_by_length(requests)
        toks, L = self._pad_batch(requests)
        B = toks.shape[0]
        batch = {"tokens": toks}
        if self.cfg.family == "encdec":
            batch["enc_frames"] = jnp.zeros(
                (B, self.cfg.encoder_seq_len, self.cfg.d_model), self.cfg.dtype
            )
        cache = self.api.init_cache(self.cfg, B, self.max_len)
        logits, cache = self._prefill(self.params, batch, cache)
        out = {r.id: [] for r in requests}
        steps = max(r.max_new_tokens for r in requests)
        tok = jnp.argmax(logits, -1)[:, None]
        for s in range(steps):
            for i, r in enumerate(requests):
                if s < r.max_new_tokens:
                    out[r.id].append(int(tok[i, 0]))
            if s + 1 < steps:  # the last emitted token needs no decode step
                logits, cache = self._decode(self.params, tok, cache, L + s)
                tok = jnp.argmax(logits, -1)[:, None]
        assert all(len(out[r.id]) == r.max_new_tokens for r in requests)
        return out
