"""Speculative decoding: a small draft model proposes, the target verifies.

Standard draft-and-verify with distribution-level rejection sampling (the
Leviathan et al. scheme): per round the draft model decodes ``gamma`` tokens
from the decoding policy's distribution q (cheap — small model), then the
target model scores all ``gamma + 1`` positions in ONE cached forward (the
same HBM traffic as a single decode step at small batch: decode is
weight-bandwidth bound, so verifying gamma+1 tokens costs roughly one token).
Draft token x is accepted with probability ``min(1, p(x)/q(x))``; on the first
rejection the replacement is sampled from ``norm(max(p - q, 0))``, and when
everything accepts the target's own next-position distribution supplies a
bonus token — so every round emits 1..gamma+1 tokens and the output is
distributed **exactly** as target-only decoding (the draft can only change
speed, never the distribution). Greedy (``temperature == 0``) is the one-hot
special case: acceptance degenerates to argmax prefix matching and the output
is token-for-token the target-only greedy sequence — the oracle the tests pin.

TPU-native specifics:

- both models follow the shared cache contract (``unionml_tpu.models.generate``),
  so rollback is free: per-example ``lengths`` simply advance by each row's
  accepted count, and stale K/V beyond that is invisible (visibility mask is
  ``slot <= position``) and overwritten by later writes — no copying, no
  per-row cache surgery, and rows with different acceptance counts coexist in
  one batch;
- the whole post-prefill generation is ONE jitted ``lax.while_loop`` dispatch
  (a host round trip per round would otherwise bound the round cost); every shape is static and emitted tokens land in a device
  output buffer via per-row ``dynamic_update_slice`` at each row's ``produced``
  offset;
- eos handling matches :class:`~unionml_tpu.models.generate.Generator`: the
  first eos in a round truncates that row's emission and marks it done.

Sampled runs are NOT key-path-compatible with the plain Generator (they consume
randomness differently), so equality holds in distribution, not per seed —
tests/unit/test_speculative.py checks both: exact tokens for greedy, empirical
distribution closeness for sampling.

Routed-expert (MoE) targets: exactness additionally requires ample expert
capacity. Capacity is sized per routed group, and the ``[B, gamma+1]`` verify
forward routes ``gamma + 1`` tokens per row where target-only decode routes one
— under a tight ``capacity_factor`` a token can be capacity-dropped in the
verify but not in plain decode (or vice versa), perturbing its logits. Size
``capacity_factor`` for ``B * (gamma + 1)`` tokens when serving MoE targets
speculatively (the MoE test here uses an ample factor for this reason).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from unionml_tpu.models.generate import GenerationConfig, Generator, PrefixCache, refuse_draft_over_slot_state

__all__ = ["SpeculativeGenerator"]


class SpeculativeGenerator:
    """Greedy speculative decoding over a (target, draft) model pair.

    >>> spec = SpeculativeGenerator(target, target_params, draft, draft_params,
    ...                             GenerationConfig(max_new_tokens=128, temperature=0.0),
    ...                             gamma=4)
    >>> tokens = spec(prompts)          # == Generator(target, ...)(prompts), faster

    ``rounds`` / ``accepted_tokens`` counters expose the realized acceptance rate
    (``accepted_tokens / (rounds * gamma)``).
    """

    def __init__(
        self,
        target_module: Any,
        target_params: Any,
        draft_module: Any,
        draft_params: Any,
        config: GenerationConfig = GenerationConfig(temperature=0.0),
        *,
        gamma: int = 4,
        mesh: Optional[Any] = None,
        partition_rules: Optional[Any] = None,
        quantize: Optional[str] = None,
        quantize_draft: Optional[str] = None,
    ):
        import dataclasses

        # strip any attached DraftSpec: the internal Generators must decode
        # plainly (a draft-bearing config would recurse through the façade)
        config = dataclasses.replace(config, draft=None)
        # reuse the Generator machinery for prefill/placement/bucketing on both
        # models. ``quantize_draft`` ("int8") stores the draft quantized too;
        # None follows the serve-wide UNIONML_TPU_QUANTIZE default inside the
        # Generator — either way the draft only proposes and the target
        # decides, so the output law is untouched
        target = Generator(
            target_module, target_params, config,
            mesh=mesh, partition_rules=partition_rules, quantize=quantize,
        )
        self._init_state(
            target,
            Generator(
                draft_module, draft_params, target.config,
                mesh=mesh, partition_rules=partition_rules, quantize=quantize_draft,
            ),
            target.config,
            gamma,
        )

    def _init_state(self, target: Generator, draft: Generator, config: GenerationConfig, gamma: int) -> None:
        """The single construction body shared by ``__init__`` and
        :meth:`from_target` — any new field must be set here, so the two paths
        cannot drift."""
        if gamma < 1:
            raise ValueError("gamma must be >= 1")
        refuse_draft_over_slot_state(target.module.config)
        self.config = config
        self.gamma = int(gamma)
        self.rounds = 0
        self.accepted_tokens = 0
        self._target = target
        self._draft = draft
        self._round_fn = None
        # (weakref-to-prefix, draft_prefix) keyed on id(prefix); a finalizer
        # drops the entry when the PrefixCache is collected, so per-tenant
        # prefixes can't accumulate both models' KV forever, and the identity
        # check guards the window before a recycled id's finalizer runs
        self._draft_prefixes: dict = {}

    @classmethod
    def from_target(cls, target: Generator, draft: "Any") -> "SpeculativeGenerator":
        """Build around an EXISTING target :class:`Generator` (whose params are
        already quantized/sharded/placed) and a
        :class:`~unionml_tpu.models.generate.DraftSpec` — the path behind
        ``GenerationConfig(draft=...)`` on the Generator façade."""
        import dataclasses

        self = cls.__new__(cls)
        config = dataclasses.replace(target.config, draft=None)
        self._init_state(
            target,
            # the DraftSpec's quantize option ("int8", or None = the serve-wide
            # UNIONML_TPU_QUANTIZE default); target.config already resolved the
            # KV dtype, so both caches share one storage dtype
            Generator(
                draft.module, draft.params, config,
                mesh=target.mesh, partition_rules=draft.partition_rules,
                quantize=draft.quantize,
            ),
            config,
            draft.gamma,
        )
        return self

    # ------------------------------------------------------------------ round

    def _build_round(self):
        gamma = int(self.gamma)
        cfg = self.config
        target, draft = self._target, self._draft
        pad = jnp.int32(cfg.pad_id)
        eos = cfg.eos_id

        # reuse each generator's jit-side apply/head closures (same fns its own
        # prefill/decode compile) rather than re-deriving the forward here
        def draft_apply(p, tok, positions, cache):
            hidden, cache = draft._apply_fn(p, tok, positions, cache, None)
            return draft._head_fn(p, hidden), cache

        def target_apply(p, tok, positions, cache, token_mask):
            hidden, cache = target._apply_fn(p, tok, positions, cache, token_mask)
            return target._head_fn(p, hidden), cache

        from unionml_tpu.models.generate import filtered_logits, policy_probs

        greedy_mode = cfg.temperature == 0.0
        cs = cfg.constraints
        if cs is not None:
            # the same tables the target Generator placed on device: both
            # models' policies mask by the DFA state along the PROPOSED path,
            # so q and p are the constrained distributions and the rejection
            # law stays exact (q's support is within p's allowed set)
            cs_trans, cs_allowed = target._cs_trans, target._cs_allowed

        def spec_round(tp, dp, t_cache, d_cache, tok, lengths, done, produced, out_buf, key, budget, *st):
            key, draft_key, corr_key = jax.random.split(key, 3)
            accept_keys = jax.random.split(draft_key, gamma + 1)

            # --- draft: gamma policy-sampled steps (small-model cached decode) ---
            def draft_body(carry, step_key):
                cache, t, ln, *s = carry
                logits, cache = draft_apply(dp, t[:, None], ln[:, None], cache)
                lg = logits[:, 0]
                if cs is not None:
                    lg = jnp.where(cs_allowed[s[0]], lg, -jnp.inf)
                if greedy_mode:
                    nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                else:
                    nxt = jax.random.categorical(step_key, filtered_logits(lg, cfg)).astype(jnp.int32)
                s_out = (s[0],) if cs is not None else ()
                s_next = (cs_trans[s[0], nxt],) if cs is not None else ()
                # emit the MASKED logits (q must be the constrained proposal
                # distribution) and the state BEFORE this position
                return (cache, nxt, ln + 1, *s_next), (nxt, lg, *s_out)

            carry_out, scanned = jax.lax.scan(
                draft_body, (d_cache, tok, lengths, *st), jax.random.split(accept_keys[gamma], gamma)
            )
            d_cache = carry_out[0]
            drafts, draft_logits = scanned[0], scanned[1]
            drafts = drafts.T  # [B, gamma]
            draft_logits = jnp.swapaxes(draft_logits, 0, 1)  # [B, gamma, V]
            if cs is not None:
                # states along the proposed path: st_ext[:, i] = state BEFORE
                # position i, for i in [0, gamma] (the bonus position included)
                st_ext = jnp.concatenate(
                    [jnp.swapaxes(scanned[2], 0, 1), carry_out[3][:, None]], axis=1
                )

            # --- draft-cache completeness: the scan fed [tok, drafts[:gamma-1]],
            # so drafts[gamma-1]'s K/V slot is never written; on an all-accept
            # round the next draft queries would attend to that zero-initialized
            # (visible) slot and acceptance would silently degrade as holes
            # accumulate. One extra headless feed fills it — for rows that
            # rejected earlier the slot is beyond their length (invisible stale
            # data, overwritten when they reach it), so the feed is always safe.
            _, d_cache = draft._apply_fn(
                dp, drafts[:, gamma - 1 :], (lengths + gamma)[:, None], d_cache, None
            )

            # --- target: score tok + all gamma drafts in one cached forward ---
            inputs = jnp.concatenate([tok[:, None], drafts], axis=1)  # [B, gamma+1]
            positions = lengths[:, None] + jnp.arange(gamma + 1)[None]
            # routed decoders consume the mask per token: broadcast row-done
            # over the full [B, gamma+1] verify width
            verify_mask = jnp.broadcast_to((~done)[:, None], inputs.shape)
            logits, t_cache = target_apply(tp, inputs, positions, t_cache, verify_mask)
            # the verify pass is this engine's decode read of the target's cache
            target.decode_attention_path = target._traced.read
            if cs is not None:
                # target logits at position i masked by the state its row
                # reached after drafts[:i] — p becomes the constrained policy
                logits = jnp.where(cs_allowed[st_ext], logits, -jnp.inf)

            # --- rejection sampling against the policy distributions ---
            # (greedy is the one-hot special case: accept iff argmaxes agree, the
            # correction/bonus is the target argmax — exactly prefix matching)
            q = policy_probs(draft_logits, cfg)  # [B, gamma, V]
            p = policy_probs(logits, cfg)  # [B, gamma+1, V]
            batch = tok.shape[0]
            still = jnp.ones((batch,), bool)
            accepted = jnp.zeros((batch,), jnp.int32)
            for i in range(gamma):  # gamma is small and static; unrolled
                x = drafts[:, i : i + 1]
                px = jnp.take_along_axis(p[:, i], x, axis=-1)[:, 0]
                qx = jnp.take_along_axis(q[:, i], x, axis=-1)[:, 0]
                u = jax.random.uniform(accept_keys[i], (batch,))
                ok = u * qx < px  # u < p(x)/q(x), division-free
                accepted = accepted + (still & ok)
                still = still & ok
            # correction (first rejection) / bonus (all accepted) token: sample
            # from norm(max(p_a - q_a, 0)) — q beyond gamma is 0, so the bonus
            # case degenerates to sampling p_gamma directly
            p_at = jnp.take_along_axis(p, accepted[:, None, None], axis=1)[:, 0]  # [B, V]
            q_ext = jnp.concatenate([q, jnp.zeros_like(q[:, :1])], axis=1)
            q_at = jnp.take_along_axis(q_ext, accepted[:, None, None], axis=1)[:, 0]
            resid = jnp.maximum(p_at - q_at, 0.0)
            # float-edge guard: a rejected position has TV(p, q) > 0 by construction,
            # but under f32 the residual can still round to all-zeros
            resid = jnp.where(resid.sum(-1, keepdims=True) > 0, resid, p_at)
            correction = jax.random.categorical(corr_key, jnp.log(resid + 1e-30)).astype(jnp.int32)

            # emitted tokens this round: accepted drafts, then the correction
            idx = jnp.arange(gamma + 1)[None]
            drafts_ext = jnp.concatenate([drafts, jnp.full((batch, 1), pad)], axis=1)
            emit_mask = idx <= accepted[:, None]
            emitted = jnp.where(idx < accepted[:, None], drafts_ext, correction[:, None])
            emitted = jnp.where(emit_mask, emitted, pad)
            if eos is not None:
                is_eos = (emitted == eos) & emit_mask
                # truncate after the first eos: positions strictly beyond it emit pad
                seen_before = jnp.cumsum(is_eos.astype(jnp.int32), axis=1) - is_eos.astype(jnp.int32)
                emit_mask = emit_mask & (seen_before == 0)
                emitted = jnp.where(emit_mask, emitted, pad)
                row_hits_eos = is_eos.any(axis=1)
            else:
                row_hits_eos = jnp.zeros_like(done)
            emitted = jnp.where(done[:, None], pad, emitted)
            n_emit = jnp.where(done, 0, emit_mask.sum(axis=1))

            # clip to each row's generation budget (per-row: continuous batching
            # admits requests with different caps into one resident batch)
            room = jnp.maximum(budget - produced, 0)
            n_emit = jnp.minimum(n_emit, room)
            emitted = jnp.where(idx < n_emit[:, None], emitted, pad)

            out_buf = jax.vmap(
                lambda buf, row, start: jax.lax.dynamic_update_slice(buf, row, (start,))
            )(out_buf, emitted, produced)

            new_done = done | row_hits_eos | (produced + n_emit >= budget)
            # next round continues after the last emitted token; finished rows freeze
            tok = jnp.where(
                new_done, tok, jnp.take_along_axis(emitted, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
            )
            lengths = lengths + jnp.where(done, 0, n_emit)
            produced = produced + n_emit
            acc_count = jnp.where(done, 0, jnp.minimum(accepted, room)).sum()
            st_next = ()
            if cs is not None:
                # the next round's DFA state: advance past the LAST emitted
                # token. Emitted tokens are a prefix of the proposed path
                # (drafts[:accepted] then the correction), so the state before
                # position j is st_ext[:, j] regardless of eos/budget clipping.
                j = jnp.maximum(n_emit - 1, 0)
                st_before = jnp.take_along_axis(st_ext, j[:, None], axis=1)[:, 0]
                last_tok = jnp.take_along_axis(emitted, j[:, None], axis=1)[:, 0]
                st_next = (jnp.where(n_emit > 0, cs_trans[st_before, last_tok], st[0]),)
            return t_cache, d_cache, tok, lengths, new_done, produced, out_buf, acc_count, key, *st_next

        def spec_loop(tp, dp, state, floor, budget):
            """Post-prefill generation as ONE device-side while_loop — per-round
            host round trips would otherwise bound the round cost. ``floor`` ([B] int32):
            keep rolling rounds while any unfinished row has produced fewer than
            its floor — ``__call__`` passes the budget (run to completion),
            :meth:`stream` and the continuous batcher pass ``produced + chunk``
            so tokens surface chunkwise with one device exit per chunk.
            ``budget`` ([B] int32) is each row's max_new_tokens cap."""
            tp = target._dequant_params(tp)
            dp = draft._dequant_params(dp)

            def cond(state):
                done_rows, produced_rows = state[4], state[5]
                return jnp.any(~done_rows & (produced_rows < floor))

            def body(state):
                t_cache, d_cache, tok, lengths, done, produced, out_buf, rounds, acc_total, key, *st = state
                t_cache, d_cache, tok, lengths, done, produced, out_buf, acc, key, *st = spec_round(
                    tp, dp, t_cache, d_cache, tok, lengths, done, produced, out_buf, key, budget, *st
                )
                return (t_cache, d_cache, tok, lengths, done, produced, out_buf, rounds + 1, acc_total + acc, key, *st)

            return jax.lax.while_loop(cond, body, state)

        # the whole state (caches, out_buf, counters) is donated and re-aliased
        # by the returned state, so repeated stream dispatches keep ONE copy in HBM
        return jax.jit(spec_loop, donate_argnums=(2,))

    # ------------------------------------------------------------------ generate

    def draft_prefix(self, prefix: PrefixCache) -> PrefixCache:
        """The DRAFT model's cache rows for a shared prefix: speculative
        decoding needs the system prompt resident in BOTH caches (the draft
        proposes conditioned on it, the target verifies conditioned on it), and
        their layer shapes differ — so the draft prefills the same token ids
        once here and the result is memoized per target-side PrefixCache."""
        import weakref

        entry = self._draft_prefixes.get(id(prefix))
        if entry is not None and entry[0]() is prefix:
            return entry[1]
        if prefix.tokens is None:
            raise ValueError(
                "prefix= with speculative decoding needs the prefix's token ids "
                "(build it with cache_prefix(...); hand-built PrefixCaches "
                "cannot be prefilled through the draft model)"
            )
        built = self._draft.cache_prefix(list(prefix.tokens))
        self._draft_prefixes[id(prefix)] = (weakref.ref(prefix), built)
        weakref.finalize(prefix, self._draft_prefixes.pop, id(prefix), None)
        return built

    def _start_state(
        self,
        prompts: Sequence[Sequence[int]],
        seed: int,
        prefix: Optional[PrefixCache] = None,
        constraint: Optional[Any] = None,
    ):
        """Prefill both models and assemble the device-side loop state:
        ``(t_cache, d_cache, tok, lengths, done, produced, out_buf, rounds,
        accepted, key[, dfa_state])``. With ``prefix``, both models get their own
        prefix rows pasted and prefill only the suffix at a ``p0`` offset —
        lengths then include the prefix, so the round loop needs no changes.
        With constraints, the target's post-tok0 DFA state rides as the state's
        tail element."""
        cfg = self.config
        if self._round_fn is None:
            self._round_fn = self._build_round()
        # prefill both models; extra cache headroom for the last round's overshoot
        n, tok0_t, _, t_carry = self._target._start(
            prompts, seed, extra_cache=self.gamma + 1, prefix=prefix, constraint=constraint
        )
        t_cache, lengths, done_t = t_carry[0], t_carry[2], t_carry[3]
        _, _, _, d_carry = self._draft._start(
            prompts, seed, extra_cache=self.gamma + 1,
            prefix=self.draft_prefix(prefix) if prefix is not None else None,
            constraint=constraint,
        )
        d_cache = d_carry[0]  # d_carry's lengths equal `lengths` (same prompts/prefix)

        batch = int(tok0_t.shape[0])
        cap = cfg.max_new_tokens + self.gamma + 1
        out_buf = jnp.full((batch, cap), cfg.pad_id, jnp.int32)
        # the prompt-sampled token is emission #1 (same as Generator's tok0;
        # with constraints the target's _start already masked it)
        out_buf = out_buf.at[:, 0].set(tok0_t)
        produced = jnp.ones((batch,), jnp.int32)
        done = done_t | (produced >= cfg.max_new_tokens)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
        st = (t_carry[5],) if cfg.constraints is not None else ()
        return n, (
            t_cache, d_cache, tok0_t, lengths, done, produced, out_buf,
            jnp.int32(0), jnp.int32(0), key, *st,
        )

    def __call__(
        self,
        prompts: Sequence[Sequence[int]],
        *,
        seed: int = 0,
        prefix: Optional[PrefixCache] = None,
        constraint: Optional[Any] = None,
    ) -> np.ndarray:
        """Generate under the config's decoding policy; greedy output is exactly
        the target-only sequence, sampled output is target-distributed. With
        ``prefix`` (from the target's ``cache_prefix``), prompts are suffixes
        after the shared prefix in BOTH models. ``constraint`` (grammar ids into
        ``config.constraints``) masks both the draft's proposals and the
        target's verify by each row's DFA state — same output law as the
        constrained plain Generator."""
        cfg = self.config
        n, state = self._start_state(prompts, seed, prefix=prefix, constraint=constraint)
        budget = jnp.full(state[2].shape, cfg.max_new_tokens, jnp.int32)
        state = self._round_fn(self._target.params, self._draft.params, state, budget, budget)
        out_buf, rounds, accepted = state[6], state[7], state[8]
        self.rounds += int(rounds)
        self.accepted_tokens += int(accepted)
        return np.asarray(out_buf)[:n, : cfg.max_new_tokens]

    def stream(
        self,
        prompts: Sequence[Sequence[int]],
        *,
        seed: int = 0,
        chunk_size: int = 16,
        prefix: Optional[PrefixCache] = None,
        constraint: Optional[Any] = None,
    ):
        """Incremental speculative generation: yields a LIST of ``len(prompts)``
        1-D int32 arrays of newly materialized tokens per row (the first yield is
        each row's prompt-sampled token). Rows advance at round granularity
        (1..gamma+1 tokens per round), so per-yield chunks are RAGGED — unlike
        :meth:`Generator.stream`'s rectangular arrays. Token totals equal
        ``__call__`` for the same seed; each dispatch rolls rounds until every
        unfinished row has at least ``chunk_size`` more tokens, so streaming
        leaves the device once per chunk, not per round."""
        cfg = self.config
        if chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        n, state = self._start_state(prompts, seed, prefix=prefix, constraint=constraint)
        prev = np.ones((n,), np.int64)
        first = np.asarray(state[6][:n, :1])  # one fetch, not one per row
        yield [first[i] for i in range(n)]
        budget = jnp.full(state[2].shape, cfg.max_new_tokens, jnp.int32)
        rounds = accepted = 0  # snapshots from the LAST SUCCESSFUL dispatch: the
        # in-flight state's buffers are donated, so reading it after a failed
        # dispatch would raise a secondary deleted-buffer error masking the cause
        try:
            while True:
                done_np = np.asarray(state[4])[:n]
                if bool(done_np.all()):
                    return
                # per-row floor: each unfinished row gains >= chunk_size tokens
                floor = jnp.minimum(state[5] + chunk_size, cfg.max_new_tokens)
                state = self._round_fn(
                    self._target.params, self._draft.params, state, floor, budget
                )
                out_np = np.asarray(state[6])
                prod_np = np.asarray(state[5])[:n]
                rounds, accepted = int(state[7]), int(state[8])
                yield [out_np[i, prev[i] : prod_np[i]] for i in range(n)]
                prev = prod_np.astype(np.int64)
        finally:
            self.rounds += rounds
            self.accepted_tokens += accepted
