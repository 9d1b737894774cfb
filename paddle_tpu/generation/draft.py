"""Draft models for speculative decoding.

Speculative decoding (the ragged engine's ``spec_tokens`` path) needs
a DRAFT: something cheap that proposes the next k tokens of every
active sequence, which the target model then verifies in ONE ragged
call. Correctness never depends on the draft — the target's greedy
tokens are emitted whatever the draft proposed (a bad draft only
lowers the accepted-token rate and with it the speedup) — so the
protocol is deliberately tiny:

    propose(contexts, k) -> list of up-to-k int arrays, one per context

``HostDraft`` is the built-in implementation: a forward pass of a
(usually smaller) GPT whose weights were pulled out of a predictor's
scope, run as one jitted greedy loop over the whole batch of contexts
— k proposal tokens for EVERY active sequence cost k tiny batched
forwards, not k x rows. ``from_predictor(pred, cfg, num_layers=n)``
truncates to the first n decoder layers for a genuinely smaller draft;
with the full layer stack the draft replicates the target and the
acceptance rate approaches 1.0 (the upper-bound configuration
tests/test_ragged.py::test_spec_decode_greedy_equivalence runs).

The draft runs OUTSIDE the ragged executable on purpose: its batch
shape is [rows, max_position] with its own (cheap) compile, and the
target executable stays byte-identical whether speculation is on or
off — flipping ``spec_tokens`` mid-fleet never recompiles the serving
step.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

__all__ = ["DraftModel", "HostDraft"]


class DraftModel:
    """Protocol: batched greedy proposal of up to k continuation
    tokens per context. Subclass and override ``propose``."""

    def propose(self, contexts: Sequence[np.ndarray],
                k: int) -> List[np.ndarray]:
        raise NotImplementedError


class HostDraft(DraftModel):
    """GPT forward over extracted weights as the draft.

    Weights live as numpy on the host; ``propose`` pads the contexts
    to one [rows, max_len] batch and runs a single jitted
    k-step greedy extension (re-prefill per proposed token — at draft
    scale the whole forward is tiny, and one fused executable beats k
    incremental host round-trips).
    """

    def __init__(self, params: dict, num_layers: int, num_heads: int,
                 max_position: int, *, name: str = "host_draft"):
        self.params = {k: np.asarray(v) for k, v in params.items()}
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.max_position = int(max_position)
        self.name = name
        # every propose() pads its row count up to at least min_rows
        # (the engine sets this to its lane count): ONE rows bucket for
        # the whole engine life instead of a compile per distinct
        # spec-row count — the draft is tiny, predictability wins
        self.min_rows = 1
        self._jitted = {}
        self._device_params = None

    # -- construction --------------------------------------------------------
    @classmethod
    def from_predictor(cls, predictor, cfg,
                       num_layers: Optional[int] = None) -> "HostDraft":
        """Extract draft weights from a loaded predictor's scope.
        ``num_layers`` truncates the decoder stack (a smaller draft);
        default keeps every layer (a replica draft — acceptance ~1)."""
        scope = predictor._scope
        n = int(num_layers if num_layers is not None else cfg.num_layers)
        names = ["gpt_tok_emb", "gpt_pos_emb",
                 "gpt_lnf.scale", "gpt_lnf.bias",
                 "gpt_head.w", "gpt_head.b"]
        for i in range(n):
            pre = f"dec{i}"
            names += [f"{pre}_ln1.scale", f"{pre}_ln1.bias",
                      f"{pre}_qkv.w", f"{pre}_qkv.b",
                      f"{pre}_proj.w", f"{pre}_proj.b",
                      f"{pre}_ln2.scale", f"{pre}_ln2.bias",
                      f"{pre}_ffn1.w", f"{pre}_ffn1.b",
                      f"{pre}_ffn2.w", f"{pre}_ffn2.b"]
        params = {}
        for name in names:
            var = scope.find_var(name)
            if var is None:
                raise ValueError(
                    f"draft weight {name!r} not in the predictor scope — "
                    "is this an LM exported by generation.build_lm_program?")
            params[name] = np.asarray(var)
        return cls(params, n, cfg.num_heads, cfg.max_position)

    # -- forward -------------------------------------------------------------
    def _fn(self, rows: int, max_len: int, k: int):
        """One jitted greedy k-extension over [rows, max_len]: a full
        prefill builds per-layer K/V caches and yields proposal 1;
        each further proposal is an INCREMENTAL single-position step
        over the caches — the draft costs ~one forward plus k-1 tiny
        extensions, not k re-prefills."""
        key = (rows, max_len, k)
        fn = self._jitted.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        # ONE device copy of the weights, shared by every shape
        # bucket's closure (a copy per bucket would multiply the
        # draft's footprint by the bucket count)
        if self._device_params is None:
            self._device_params = {n: jnp.asarray(v)
                                   for n, v in self.params.items()}
        p = self._device_params
        H = self.num_heads
        L = max_len

        def ln(x, pre):
            mu = x.mean(-1, keepdims=True)
            var = ((x - mu) ** 2).mean(-1, keepdims=True)
            return ((x - mu) / jnp.sqrt(var + 1e-5)
                    ) * p[f"{pre}.scale"] + p[f"{pre}.bias"]

        def head_logits(x):
            return ln(x, "gpt_lnf") @ p["gpt_head.w"] + p["gpt_head.b"]

        def prefill(toks, lens):
            # toks [R, L] int32 -> (argmax at each row's last token,
            # per-layer K/V caches [R, L, H*D])
            R = toks.shape[0]
            x = p["gpt_tok_emb"][toks] + p["gpt_pos_emb"][None, :L]
            kpmask = (jnp.arange(L)[None, :] < lens[:, None])
            causal = jnp.tril(jnp.ones((L, L), bool))
            caches = []
            for i in range(self.num_layers):
                pre = f"dec{i}"
                h = ln(x, f"{pre}_ln1")
                qkv = h @ p[f"{pre}_qkv.w"] + p[f"{pre}_qkv.b"]
                q, kk, v = jnp.split(qkv, 3, axis=-1)
                caches.append((kk, v))
                D = q.shape[-1] // H

                def heads(t):
                    return t.reshape(R, L, H, D).transpose(0, 2, 1, 3)

                s = jnp.einsum("rhqd,rhkd->rhqk", heads(q),
                               heads(kk)) / jnp.sqrt(D).astype(x.dtype)
                s = jnp.where(causal[None, None], s, -1e9)
                s = jnp.where(kpmask[:, None, None, :], s, -1e9)
                ctx = jnp.einsum("rhqk,rhkd->rhqd", jax.nn.softmax(s, -1),
                                 heads(v))
                ctx = ctx.transpose(0, 2, 1, 3).reshape(R, L, -1)
                x = x + ctx @ p[f"{pre}_proj.w"] + p[f"{pre}_proj.b"]
                h2 = ln(x, f"{pre}_ln2")
                f1 = jax.nn.gelu(
                    h2 @ p[f"{pre}_ffn1.w"] + p[f"{pre}_ffn1.b"],
                    approximate=False)
                x = x + f1 @ p[f"{pre}_ffn2.w"] + p[f"{pre}_ffn2.b"]
            logits = head_logits(x)
            last = jnp.take_along_axis(
                logits, (lens - 1)[:, None, None].astype(jnp.int32), axis=1)
            return jnp.argmax(last[:, 0], -1).astype(jnp.int32), caches

        def step(tok, pos, lens, caches):
            # one new token per row at position pos [R] over the caches
            R = tok.shape[0]
            x = (p["gpt_tok_emb"][tok][:, None]
                 + p["gpt_pos_emb"][jnp.minimum(pos, L - 1)][:, None])
            new_caches = []
            attend = (jnp.arange(L)[None, :] <= pos[:, None])   # [R, L]
            for i, (ck, cv) in enumerate(caches):
                pre = f"dec{i}"
                h = ln(x, f"{pre}_ln1")
                qkv = h @ p[f"{pre}_qkv.w"] + p[f"{pre}_qkv.b"]
                q, kk, v = jnp.split(qkv, 3, axis=-1)
                idx = jnp.minimum(pos, L - 1)
                ck = ck.at[jnp.arange(R), idx].set(kk[:, 0])
                cv = cv.at[jnp.arange(R), idx].set(v[:, 0])
                new_caches.append((ck, cv))
                D = q.shape[-1] // H
                qh = q.reshape(R, H, D)
                kh = ck.reshape(R, L, H, D)
                vh = cv.reshape(R, L, H, D)
                s = jnp.einsum("rhd,rlhd->rhl", qh,
                               kh) / jnp.sqrt(D).astype(x.dtype)
                s = jnp.where(attend[:, None, :], s, -1e9)
                ctx = jnp.einsum("rhl,rlhd->rhd", jax.nn.softmax(s, -1),
                                 vh).reshape(R, 1, -1)
                x = x + ctx @ p[f"{pre}_proj.w"] + p[f"{pre}_proj.b"]
                h2 = ln(x, f"{pre}_ln2")
                f1 = jax.nn.gelu(
                    h2 @ p[f"{pre}_ffn1.w"] + p[f"{pre}_ffn1.b"],
                    approximate=False)
                x = x + f1 @ p[f"{pre}_ffn2.w"] + p[f"{pre}_ffn2.b"]
            logits = head_logits(x)
            return jnp.argmax(logits[:, 0], -1).astype(jnp.int32), new_caches

        def extend(toks, lens):
            nxt, caches = prefill(toks, lens)
            out = [nxt]
            pos = lens
            for _ in range(k - 1):
                nxt, caches = step(nxt, pos, lens, caches)
                pos = pos + 1
                out.append(nxt)
            return jnp.stack(out, axis=1)        # [R, k]

        fn = jax.jit(extend)
        self._jitted[key] = fn
        return fn

    def warmup(self, k: int) -> None:
        """Compile every (rows, length) bucket ``propose`` can hit —
        the engine's warmup calls this so no serving step ever pays a
        draft XLA compile mid-generation (the same contract the
        target executable's warmup keeps)."""
        if k < 1:
            return
        b = 16
        seen = set()
        while True:
            cap = min(self.max_position, b)
            if cap not in seen:
                seen.add(cap)
                self.propose([np.zeros(max(1, cap - k), np.int64)], k)
            if cap >= self.max_position:
                return
            b *= 2

    def propose(self, contexts: Sequence[np.ndarray],
                k: int) -> List[np.ndarray]:
        if not contexts or k < 1:
            return [np.zeros(0, np.int64) for _ in contexts]
        rows = len(contexts)
        lens = np.array([len(c) for c in contexts], np.int32)
        # bucket BOTH dims (rows to a pow-2 floor of min_rows, lengths
        # to a pow-2 ladder) so a handful of executables serves every
        # batch shape the engine's churn produces — a compile per
        # distinct row count would burn the very steps speculation
        # saves (and warmup() can pre-pay the whole ladder)
        rows_b = 1 << (max(rows, self.min_rows) - 1).bit_length()
        need = int(lens.max()) + k
        max_len = min(self.max_position,
                      max(16, 1 << (need - 1).bit_length()))
        toks = np.zeros((rows_b, max_len), np.int32)
        for i, c in enumerate(contexts):
            toks[i, :len(c)] = np.asarray(c, np.int64)[:max_len]
        pad_lens = np.ones(rows_b, np.int32)
        pad_lens[:rows] = lens
        ks = np.asarray(self._fn(rows_b, max_len, k)(toks, pad_lens))
        out = []
        for i in range(rows):
            # never propose past the position window (the engine caps
            # against its own page/budget limits on top)
            room = max(0, self.max_position - int(lens[i]) - 1)
            out.append(ks[i, :min(k, room)].astype(np.int64))
        return out
