"""Grammar-constrained (structured) decoding.

Oracles, mirroring the generation ring's style (tests/unit/test_generate.py):

- compiler level: the token DFA's ``allowed``/``trans``/EOS columns are checked
  against Python ``re.fullmatch`` over enumerated token sequences — acceptance
  (EOS allowed) must equal full-match of the concatenated text, and every
  allowed token must keep the text extendable to a sentence of the language
  (token-level liveness);
- engine level: greedy/sampled decoding under a constraint must emit text the
  grammar full-matches (or a legal prefix when the budget truncates), the FREE
  grammar must be byte-identical to an unconstrained generator, and the
  continuous batcher's concurrent constrained streams must equal their solo
  ``Generator.__call__(constraint=...)`` runs token-exactly.

The reference has no generation surface at all (SURVEY.md §2.3); structured
output is new TPU-native capability: the grammar is data (device tables), not
control flow, so one compiled decode program serves every grammar.
"""

import re
from typing import List

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.models import (
    ConstraintSet,
    DraftSpec,
    GenerationConfig,
    Generator,
    Llama,
    LlamaConfig,
    TokenConstraint,
    compile_regex,
    literal_choice,
)

EOS = 96


def _texts() -> List[str]:
    """Token id -> decoded text for the tiny vocab: ids 1-26 = a-z, 27-36 =
    digits, a few multi-char BPE-style pieces, everything else (incl. pad 0 and
    eos 96) decodes empty."""
    texts = [""] * 97
    for i in range(26):
        texts[1 + i] = chr(ord("a") + i)
    for i in range(10):
        texts[27 + i] = str(i)
    texts[40], texts[41], texts[42] = "ab", "12", "3.5"
    return texts


TEXTS = _texts()


@pytest.fixture(scope="module")
def tiny():
    config = LlamaConfig.tiny(
        vocab_size=97, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return module, params, config


def decode_text(row, texts=TEXTS) -> str:
    out = ""
    for t in np.asarray(row).tolist():
        if t == EOS:
            break
        out += texts[t]
    return out


# ---------------------------------------------------------------------- compiler


def test_token_dfa_acceptance_equals_re_fullmatch():
    """Walk every token sequence up to depth 3 over a small vocab: the DFA must
    allow exactly the extendable ones, and allow EOS exactly at full matches."""
    vocab = ["", "a", "b", "ab", "c", "cc"]
    pattern = r"(ab|b)*c{1,2}"
    c = compile_regex(pattern, vocab, eos_id=0)
    alphabet = "abc"

    # brute-force the language up to 8 chars (regular + short)
    def strings(prefix, depth):
        yield prefix
        if depth == 0:
            return
        for ch in alphabet:
            yield from strings(prefix + ch, depth - 1)
    lang = {s for s in strings("", 8) if re.fullmatch(pattern, s)}

    def extendable(text: str) -> bool:
        return any(s.startswith(text) for s in lang)

    seqs = [((), 0, "")]
    for _ in range(3):
        nxt = []
        for toks, state, text in seqs:
            # EOS column == exact acceptance
            assert bool(c.allowed[state, 0]) == bool(re.fullmatch(pattern, text)), (toks, text)
            for t, tx in enumerate(vocab):
                if t == 0:
                    continue
                ok = bool(c.allowed[state, t])
                assert ok == extendable(text + tx), (text, tx)
                if ok:
                    nxt.append((toks + (t,), int(c.trans[state, t]), text + tx))
        seqs = nxt


def test_empty_match_allows_immediate_eos():
    c = compile_regex(r"(ab)*", ["", "ab"], eos_id=0)
    assert bool(c.allowed[0, 0])


def test_bounded_quantifier():
    c = compile_regex("a{2,3}", ["", "a", "aa"], eos_id=0)
    s1 = int(c.trans[0, 1])
    assert not c.allowed[s1, 0]  # "a": not yet a sentence
    s2 = int(c.trans[s1, 1])
    assert c.allowed[s2, 0]  # "aa"
    s3 = int(c.trans[s2, 1])
    assert c.allowed[s3, 0] and not c.allowed[s3, 1]  # "aaa" is maximal
    # the two-char token takes the same states
    assert int(c.trans[0, 2]) == s2


def test_char_classes_and_escapes():
    vocab = ["", "a", "Z", "_", "7", " ", "-"]
    c = compile_regex(r"\w+", vocab, eos_id=0)
    for t in (1, 2, 3, 4):
        assert c.allowed[0, t]
    for t in (5, 6):
        assert not c.allowed[0, t]
    neg = compile_regex(r"[^0-9]+", vocab, eos_id=0)
    assert neg.allowed[0, 1] and not neg.allowed[0, 4]


def test_literal_choice_tokenization_paths():
    vocab = ["", "y", "es", "yes", "n", "o", "no", "s"]
    c = literal_choice(["yes", "no"], vocab, eos_id=0)
    start_ok = {vocab[t] for t in range(len(vocab)) if c.allowed[0, t]}
    assert start_ok == {"y", "yes", "n", "no"}
    s_yes = int(c.trans[0, 3])
    assert c.allowed[s_yes, 0]  # complete
    assert not c.allowed[s_yes, 7]  # "yess" escapes the language


def test_malformed_brace_is_literal_like_re():
    """``re`` treats non-quantifier braces as literals; the compiler must not
    silently parse them as quantifiers (a{-2} once compiled to the
    empty-string language)."""
    vocab = ["", "a", "{", "-", "2", "}", " ", ",", "3", "4"]
    for pat in ("a{-2}", "a{ 2}", "a{}", "a{2,3,4}"):
        c = compile_regex(pat, vocab, eos_id=0)
        state = 0
        for ch in pat:
            t = vocab.index(ch)
            assert c.allowed[state, t], (pat, ch)
            state = int(c.trans[state, t])
        assert c.allowed[state, 0], pat  # the literal text is a full match
        assert re.fullmatch(re.escape(pat) if False else pat, pat), pat


def test_open_ended_brace_quantifiers():
    vocab = ["", "a"]
    c = compile_regex("a{,2}", vocab, eos_id=0)  # 0-2 a's
    assert c.allowed[0, 0]
    s1 = int(c.trans[0, 1])
    s2 = int(c.trans[s1, 1])
    assert c.allowed[s2, 0] and not c.allowed[s2, 1]
    # Python 3.12 treats bare {,} as {0,}
    c = compile_regex("a{,}", vocab, eos_id=0)
    assert c.allowed[0, 0]
    s = int(c.trans[0, 1])
    assert c.allowed[s, 0] and c.allowed[s, 1]


def test_dangling_escape_in_class_raises_valueerror():
    with pytest.raises(ValueError, match="dangling backslash"):
        compile_regex("[\\", ["", "a"], eos_id=0)
    with pytest.raises(ValueError, match="quantifier bounds"):
        compile_regex("a{3,2}", ["", "a"], eos_id=0)


def test_unrealizable_grammar_raises():
    with pytest.raises(ValueError, match="unreachable with this vocabulary"):
        compile_regex("[0-9]+", ["", "a", "b"], eos_id=0)


def test_empty_string_tokens_never_allowed():
    c = compile_regex("a*", ["", "a", ""], eos_id=0)
    assert not c.allowed[:, 2].any()


def test_anchors_are_noops_under_fullmatch():
    """``^[ab]+$`` — the most common full-match spelling — must compile to the
    same language as ``[ab]+``, not demand literal '^'/'$' characters."""
    vocab = ["", "a", "b", "^", "$"]
    c = compile_regex(r"^[ab]+$", vocab, eos_id=0)
    assert c.allowed[0, 1] and c.allowed[0, 2]
    assert not c.allowed[0, 3] and not c.allowed[0, 4]  # no literal anchors
    s = int(c.trans[0, 1])
    assert c.allowed[s, 0]  # "a" is a full match
    # redundant / repeated anchors and top-level per-branch anchors, as re allows
    for pat, tok in ((r"^^a$$", 1), (r"^a|b$", 1), (r"^a|^b", 1)):
        c = compile_regex(pat, vocab, eos_id=0)
        st = int(c.trans[0, tok])
        assert c.allowed[st, 0], pat


def test_mid_pattern_anchor_raises_escaped_is_literal():
    """Anchors anywhere but top-level pattern edges are parse errors: mid-branch
    they match nothing under fullmatch, and at GROUP branch edges (`(a$)b`,
    `a(^b)`) a no-op would silently accept strings re.fullmatch rejects."""
    for pat in (r"a^b", r"a$b", r"a+$b", r"(a$)b", r"a(^b)", r"(^a)b", r"(^a)|(b$)"):
        with pytest.raises(ValueError, match="anchor"):
            compile_regex(pat, ["", "a", "b"], eos_id=0)
    vocab = ["", "a", "^", "$"]
    c = compile_regex(r"\^a\$", vocab, eos_id=0)  # escaped = literal, as before
    s = int(c.trans[0, 2])
    s = int(c.trans[s, 1])
    s = int(c.trans[s, 3])
    assert c.allowed[s, 0]
    assert re.fullmatch(r"\^a\$", "^a$")


def test_json_object_grammar():
    import json as jsonlib

    from unionml_tpu.models import json_object

    chars = sorted(set('abcdefghijklmnopqrstuvwxyz0123456789"{}:,.-+eE \t\ntruefalsnul'))
    vocab = [""] + chars
    g = json_object({"name": "string", "age": "integer", "ok": "boolean"}, vocab, eos_id=0)

    def accepts(text: str) -> bool:
        st = 0
        for ch in text:
            t = vocab.index(ch)
            if not g.allowed[st, t]:
                return False
            st = int(g.trans[st, t])
        return bool(g.allowed[st, 0])

    good = '{"name": "ada", "age": 36, "ok": true}'
    assert accepts(good) and jsonlib.loads(good)["age"] == 36
    assert accepts('{"name":"x","age":0,"ok":false}')  # minimal whitespace
    assert not accepts('{"name": "ada"}')  # missing keys
    assert not accepts('{"age": 36, "name": "ada", "ok": true}')  # wrong order
    assert not accepts('{"name": "ada", "age": 01, "ok": true}')  # leading zero
    with pytest.raises(ValueError, match="non-empty"):
        json_object({}, vocab, eos_id=0)
    with pytest.raises(ValueError, match="JSON escaping"):
        json_object({'a"b': "string"}, vocab, eos_id=0)
    with pytest.raises(ValueError, match="unknown value type"):
        json_object({"ok": "bool"}, vocab, eos_id=0)  # typo for 'boolean'


def test_vocab_from_tokenizer_gpt2_bpe(tmp_path):
    """An offline GPT2-style BPE tokenizer round-trips through the extracted
    vocab: joining per-id texts over encode(s) reproduces s (the property the
    grammar compiler needs)."""
    import json as jsonlib

    transformers = pytest.importorskip("transformers")

    vocab = {"<|endoftext|>": 0, "a": 1, "b": 2, "ab": 3, "Ġ": 4, "Ġa": 5,
             "c": 6, "1": 7, "2": 8, "12": 9}
    (tmp_path / "vocab.json").write_text(jsonlib.dumps(vocab))
    (tmp_path / "merges.txt").write_text("#version: 0.2\na b\nĠ a\n1 2\n")
    tok = transformers.GPT2Tokenizer(str(tmp_path / "vocab.json"), str(tmp_path / "merges.txt"))

    from unionml_tpu.models import compile_regex, vocab_from_tokenizer

    texts = vocab_from_tokenizer(tok)
    assert texts[0] == ""  # special token masked out
    assert texts[4] == " " and texts[5] == " a"  # BPE space marker decoded
    s = "ab a12"
    ids = tok.encode(s, add_special_tokens=False)
    assert "".join(texts[t] for t in ids) == s

    # and the extracted vocab drives the compiler: 'ab' reachable, digits too
    c = compile_regex(r"(ab)+ a[0-9]+", texts, eos_id=0)
    st = 0
    for t in ids:  # "ab" " a" "12" spells a sentence of the language
        assert c.allowed[st, t]
        st = int(c.trans[st, t])
    assert c.allowed[st, 0]


def test_vocab_from_tokenizer_sentencepiece_space():
    """transformers' sentencepiece detok strips a word-initial ▁'s space when
    the token is first in the sequence — per-id extraction makes EVERY token
    first, which would silently drop all inter-word spaces. The extractor must
    re-prepend it."""
    from unionml_tpu.models import vocab_from_tokenizer

    class FakeSP:
        vocab_size = 5
        all_special_ids = [0]
        added_tokens_encoder = {}
        _toks = {0: "<s>", 1: "▁the", 2: "ing", 3: "▁", 4: "a"}

        def convert_ids_to_tokens(self, i):
            return self._toks[i]

        def convert_tokens_to_string(self, tokens):
            # mimic LlamaTokenizer: strip the FIRST token's leading ▁
            first = tokens[0]
            if first.startswith("▁"):
                first = first[1:]
            return first + "".join(t.replace("▁", " ") for t in tokens[1:])

    texts = vocab_from_tokenizer(FakeSP())
    assert texts == ["", " the", "ing", " ", "a"]


def test_constraint_set_layout():
    vocab = ["", "a", "b"]
    g1 = compile_regex("a+", vocab, eos_id=0)
    g2 = compile_regex("b+", vocab, eos_id=0)
    cs = ConstraintSet([g1, g2])
    assert cs.n_grammars == 3  # FREE + 2
    assert bool(cs.allowed[0].all())  # FREE allows everything
    s = int(cs.starts[1])
    assert cs.allowed[s, 1] and not cs.allowed[s, 2]
    s = int(cs.starts[2])
    assert cs.allowed[s, 2] and not cs.allowed[s, 1]
    with pytest.raises(ValueError, match="grammar id"):
        cs.start_states([3])
    with pytest.raises(ValueError, match="share one vocab"):
        ConstraintSet([g1, compile_regex("a", ["", "a"], eos_id=0)])


# ------------------------------------------------------------------- generator


@pytest.fixture(scope="module")
def cs():
    return ConstraintSet(
        [
            compile_regex(r"[a-c]{3,5}", TEXTS, eos_id=EOS),
            compile_regex(r"-?[0-9]+(\.[0-9]+)?", TEXTS, eos_id=EOS),
        ]
    )


def test_greedy_generation_satisfies_grammar(tiny, cs):
    module, params, _ = tiny
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=10, temperature=0.0, eos_id=EOS,
                         prompt_buckets=(8,), constraints=cs),
    )
    out = gen([[3, 14, 15], [7, 7, 9]], constraint=[1, 2])
    text0, text1 = decode_text(out[0]), decode_text(out[1])
    assert re.fullmatch(r"[a-c]{3,5}", text0), text0
    # the digit grammar may be budget-truncated: full match or legal prefix
    assert re.fullmatch(r"-?[0-9]+(\.[0-9]+)?", text1) or re.fullmatch(
        r"-?[0-9]*(\.[0-9]*)?", text1
    ), text1


def test_free_grammar_matches_unconstrained(tiny, cs):
    module, params, _ = tiny
    kw = dict(max_new_tokens=8, temperature=0.0, eos_id=EOS, prompt_buckets=(8,))
    gen_cs = Generator(module, params, GenerationConfig(constraints=cs, **kw))
    gen_plain = Generator(module, params, GenerationConfig(**kw))
    prompts = [[5, 6, 7], [1, 2]]
    assert np.array_equal(gen_cs(prompts), gen_plain(prompts))
    assert np.array_equal(gen_cs(prompts, constraint=0), gen_plain(prompts))


def test_sampled_generation_satisfies_grammar(tiny, cs):
    module, params, _ = tiny
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=12, temperature=1.0, eos_id=EOS,
                         prompt_buckets=(8,), constraints=cs),
    )
    for seed in range(4):
        text = decode_text(gen([[2, 3]], seed=seed, constraint=1)[0])
        assert re.fullmatch(r"[a-c]{3,5}", text) or (
            len(text) < 3 and all(ch in "abc" for ch in text)
        ), (seed, text)


def test_stream_matches_call_constrained(tiny, cs):
    module, params, _ = tiny
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=9, temperature=0.0, eos_id=EOS,
                         prompt_buckets=(8,), constraints=cs),
    )
    prompts = [[3, 14, 15], [7, 9]]
    ref = gen(prompts, constraint=[1, 2])
    chunks = list(gen.stream(prompts, chunk_size=3, constraint=[1, 2]))
    got = np.concatenate(chunks, axis=1)
    assert np.array_equal(got, ref[:, : got.shape[1]])


def test_prefix_cache_composes_with_constraint(tiny, cs):
    module, params, _ = tiny
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=6, temperature=0.0, eos_id=EOS,
                         prompt_buckets=(8,), constraints=cs),
    )
    prefix = gen.cache_prefix([11, 12, 13])
    out = gen([[3, 14]], prefix=prefix, constraint=1)
    full = gen([[11, 12, 13, 3, 14]], constraint=1)
    assert np.array_equal(out, full)


def test_int8_quantized_generation_composes_with_constraints(tiny, cs):
    """Weight-only int8 x grammar: the mask applies to logits after the
    dequant-fused forward, so quantized constrained outputs still satisfy the
    grammar (exact token equality with bf16 is not expected — quantization
    legitimately perturbs logits)."""
    module, params, _ = tiny
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=10, temperature=0.0, eos_id=EOS,
                         prompt_buckets=(8,), constraints=cs),
        quantize="int8",
    )
    text = decode_text(gen([[3, 14, 15]], constraint=1)[0])
    # binding: the DFA forbids eos before 3 chars and forces it by 5, and the
    # 10-token budget always covers 5 single-char tokens — a correct run MUST
    # full-match (a prefix fallback would also accept an early-eos mask bug)
    assert re.fullmatch(r"[a-c]{3,5}", text), text


def test_constraint_without_set_raises(tiny):
    module, params, _ = tiny
    gen = Generator(module, params, GenerationConfig(max_new_tokens=4, prompt_buckets=(8,)))
    with pytest.raises(ValueError, match="requires GenerationConfig.constraints"):
        gen([[1, 2]], constraint=1)


def test_wrong_constraint_arity_raises(tiny, cs):
    module, params, _ = tiny
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=4, prompt_buckets=(8,), constraints=cs),
    )
    with pytest.raises(ValueError, match="entries for"):
        gen([[1, 2]], constraint=[1, 2])




MICRO_TEXTS = ["", "a", "b", "c", "d", ""]  # ids 1-4 decode a-d; 5 = eos
MICRO_EOS = 5


def _micro_cs(pattern: str) -> ConstraintSet:
    return ConstraintSet([compile_regex(pattern, MICRO_TEXTS, eos_id=MICRO_EOS)])


def _constrained_brute_force(module, params, cset, grammar, prompt, steps):
    """Enumerate every DFA-legal continuation (eos freezes the row; pads
    after), scoring with the CONSTRAINED policy: logits masked by the state's
    allowed set, then log-renormalized — exactly beam_fn's logprobs. Walks
    the ConstraintSet's union table from the grammar's start state."""
    import itertools

    best, best_score = None, -np.inf
    for cont in itertools.product(range(len(MICRO_TEXTS)), repeat=steps):
        tokens, score, finished, legal = list(prompt), 0.0, False, True
        state = int(cset.starts[grammar])
        for t in cont:
            if finished:
                legal = t == 0  # pad after eos
                if not legal:
                    break
                continue
            if not cset.allowed[state, t]:
                legal = False
                break
            logits = module.apply({"params": params}, jnp.asarray([tokens], jnp.int32))
            row = np.asarray(logits[0, -1], np.float64)
            row[~np.asarray(cset.allowed[state], bool)] = -np.inf
            m = row.max()
            lp = row - (np.log(np.sum(np.exp(row - m))) + m)
            score += float(lp[t])
            state = int(cset.trans[state, t])
            tokens.append(t)
            if t == MICRO_EOS:
                finished = True
        if legal and score > best_score:
            best, best_score = list(cont), score
    return best, best_score


@pytest.mark.slow  # brute-force V^steps oracle, ~28s — outside the tier-1 budget
def test_constrained_full_width_beam_equals_exhaustive(micro_lm):
    module, params, _ = micro_lm
    steps = 3
    cset = _micro_cs("[a-c]{2,3}")
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=steps, temperature=0.0, eos_id=MICRO_EOS,
                         prompt_buckets=(8,), constraints=cset),
    )
    for prompt in ([1, 4, 2], [3, 2]):
        best, _ = _constrained_brute_force(module, params, cset, 1, prompt, steps)
        out = gen.beam_search([prompt], num_beams=len(MICRO_TEXTS) ** (steps - 1), constraint=1)
        assert out[0].tolist() == best, (prompt, best)
        # and the winner spells a sentence (or budget-truncated prefix) of the language
        text = "".join(MICRO_TEXTS[t] for t in out[0] if t not in (0, MICRO_EOS))
        assert re.fullmatch(r"[a-c]{2,3}", text) or (len(text) <= 3 and all(ch in "abc" for ch in text))


def test_constrained_beam_one_equals_greedy(micro_lm):
    module, params, _ = micro_lm
    cset = _micro_cs("[a-c]{2,4}")
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=6, temperature=0.0, eos_id=MICRO_EOS,
                         prompt_buckets=(8,), constraints=cset),
    )
    prompts = [[1, 2, 3], [4, 2]]
    greedy = gen(prompts, constraint=[1, 1])
    beam = gen.beam_search(prompts, num_beams=1, constraint=[1, 1])
    assert np.array_equal(beam, greedy)


def test_constrained_beam_free_grammar_matches_unconstrained(micro_lm):
    module, params, _ = micro_lm
    cset = _micro_cs("[a-c]+")
    kw = dict(max_new_tokens=5, temperature=0.0, prompt_buckets=(8,))
    gen_cs = Generator(module, params, GenerationConfig(constraints=cset, **kw))
    gen_plain = Generator(module, params, GenerationConfig(**kw))
    prompts = [[1, 2], [3]]
    assert np.array_equal(
        gen_cs.beam_search(prompts, num_beams=3, constraint=0),
        gen_plain.beam_search(prompts, num_beams=3),
    )


def test_stop_sequences_automaton_matches_re_search():
    """Property check vs re.search over all token sequences up to depth 4: a
    walk is allowed exactly while no stop string has completed strictly inside
    an emitted token, and the must-EOS state is entered exactly when the text
    ends with a stop."""
    from unionml_tpu.models import stop_sequences

    vocab = ["", "a", "b", "ab", "ba", "bb"]
    stops = ["abb", "bb"]
    c = stop_sequences(stops, vocab, eos_id=0)

    def ends_with_stop(text):
        return any(text.endswith(s) for s in stops)

    def contains_stop_inside(prev, tok):
        # a stop completing strictly before the token's last char
        text = prev + tok
        for i in range(len(prev) + 1, len(text)):
            if any(text[:i].endswith(s) for s in stops):
                return True
        return False

    frontier = [(0, "")]
    for _ in range(4):
        nxt = []
        for state, text in frontier:
            at_stop = ends_with_stop(text)
            for t in range(1, len(vocab)):
                ok = bool(c.allowed[state, t])
                if at_stop:
                    assert not ok, (text, vocab[t])
                    continue
                expected = not contains_stop_inside(text, vocab[t])
                assert ok == expected, (text, vocab[t])
                if ok:
                    nxt.append((int(c.trans[state, t]), text + vocab[t]))
            assert bool(c.allowed[state, 0])  # eos always available
        frontier = nxt


def test_stop_sequences_end_generation(tiny):
    """Engine-level: with a stop constraint, greedy output either ends with the
    stop string (followed by eos) or never contains it."""
    from unionml_tpu.models import stop_sequences

    module, params, _ = tiny
    stops = ["ab", "ca"]
    cset = ConstraintSet([stop_sequences(stops, TEXTS, eos_id=EOS)])
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=12, temperature=0.0, eos_id=EOS,
                         prompt_buckets=(8,), constraints=cset),
    )
    for seed_prompt in ([3, 14, 15], [1, 2], [7, 9]):
        row = gen([seed_prompt], constraint=1)[0].tolist()
        text, hit_eos, n_emitted = "", False, 0
        for t in row:
            n_emitted += 1
            if t == EOS:
                hit_eos = True
                break
            text += TEXTS[t]
        occurrences = [i for s in stops for i in range(len(text)) if text[i:].startswith(s)]
        if any(text.endswith(s) for s in stops):
            # stop completed -> eos is FORCED on the very next step (only a
            # budget that ran out exactly at the stop's last token excuses it)
            assert hit_eos or n_emitted == 12, (text, row)
            # and the stop appears ONLY at the very end
            assert all(i + len(s) >= len(text) for s in stops for i in occurrences if text[i:].startswith(s))
        else:
            assert not occurrences, text


# -------------------------------------------------- speculative composition


def _draft_pair(tiny):
    """A half-trained 'draft': same architecture, different init — realistic
    imperfect agreement with the target."""
    module, params, _ = tiny
    d_params = module.init(jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32))["params"]
    return module, d_params


def test_speculative_constrained_greedy_equals_target_only(tiny, cs):
    """The composition oracle: greedy speculative decoding under a grammar is
    token-exact against the constrained PLAIN Generator — the draft can change
    speed, never tokens, constrained or not."""
    module, params, _ = tiny
    d_module, d_params = _draft_pair(tiny)
    plain = Generator(
        module, params,
        GenerationConfig(max_new_tokens=10, temperature=0.0, eos_id=EOS,
                         prompt_buckets=(8,), constraints=cs),
    )
    spec = Generator(
        module, params,
        GenerationConfig(
            max_new_tokens=10, temperature=0.0, eos_id=EOS, prompt_buckets=(8,),
            constraints=cs, draft=DraftSpec(module=d_module, params=d_params, gamma=3),
        ),
    )
    prompts = [[3, 14, 15], [7, 7, 9]]
    for gids in ([1, 2], [2, 1], [0, 1]):
        assert np.array_equal(spec(prompts, constraint=gids), plain(prompts, constraint=gids)), gids


def test_speculative_constrained_sampled_satisfies_grammar(tiny, cs):
    module, params, _ = tiny
    d_module, d_params = _draft_pair(tiny)
    spec = Generator(
        module, params,
        GenerationConfig(
            max_new_tokens=12, temperature=1.0, eos_id=EOS, prompt_buckets=(8,),
            constraints=cs, draft=DraftSpec(module=d_module, params=d_params, gamma=3),
        ),
    )
    for seed in range(3):
        text = decode_text(spec([[2, 3]], seed=seed, constraint=1)[0])
        assert re.fullmatch(r"[a-c]{3,5}", text) or (
            len(text) < 3 and all(ch in "abc" for ch in text)
        ), (seed, text)


def test_speculative_constrained_stream_matches_call(tiny, cs):
    """The draft path's stream() must thread constraint= too: per-row ragged
    chunks concatenate to exactly __call__'s emitted tokens."""
    module, params, _ = tiny
    d_module, d_params = _draft_pair(tiny)
    spec = Generator(
        module, params,
        GenerationConfig(
            max_new_tokens=9, temperature=0.0, eos_id=EOS, prompt_buckets=(8,),
            constraints=cs, draft=DraftSpec(module=d_module, params=d_params, gamma=3),
        ),
    )
    prompts = [[3, 14, 15], [7, 9]]
    ref = spec(prompts, constraint=[1, 2])
    rows = [[] for _ in prompts]
    for chunk in spec.stream(prompts, chunk_size=3, constraint=[1, 2]):
        for i, arr in enumerate(chunk):
            rows[i].extend(int(t) for t in arr)
    for i, got in enumerate(rows):
        assert got == ref[i, : len(got)].tolist(), i
        # stream stops at eos; __call__ pads the remainder
        assert all(int(t) == 0 for t in ref[i, len(got) :]), i


def test_speculative_constrained_composes_with_prefix(tiny, cs):
    """The full matrix cell: draft x grammar x shared system prompt."""
    module, params, _ = tiny
    d_module, d_params = _draft_pair(tiny)
    spec = Generator(
        module, params,
        GenerationConfig(
            max_new_tokens=6, temperature=0.0, eos_id=EOS, prompt_buckets=(8,),
            constraints=cs, draft=DraftSpec(module=d_module, params=d_params, gamma=2),
        ),
    )
    prefix = spec.cache_prefix([11, 12, 13])
    out = spec([[3, 14]], prefix=prefix, constraint=1)
    full = spec([[11, 12, 13, 3, 14]], constraint=1)
    assert np.array_equal(out, full)


@pytest.mark.parametrize("sizes", [{}, {"block_size": 4}], ids=["one_block_a_row", "blocks_of_4"])
def test_continuous_speculative_constrained_matches_solo(tiny, cs, sizes):
    """The last matrix cell: concurrent speculative streams with per-request
    grammars through the shared batcher equal their solo constrained runs."""
    from unionml_tpu.serving import ContinuousBatcher

    module, params, _ = tiny
    d_module, d_params = _draft_pair(tiny)
    gen = Generator(
        module, params,
        GenerationConfig(
            max_new_tokens=8, temperature=0.0, eos_id=EOS, prompt_buckets=(8,),
            constraints=cs, draft=DraftSpec(module=d_module, params=d_params, gamma=3),
        ),
    )
    prompts = [[3, 14, 15], [7, 7, 9], [1, 2]]
    gids = [1, 2, 0]
    solo = [_solo_until_eos(gen, p, g) for p, g in zip(prompts, gids)]
    batcher = ContinuousBatcher(gen, slots=2, decode_chunk=2, **sizes)
    try:
        streams = [batcher.submit(p, constraint=g) for p, g in zip(prompts, gids)]
        for got_stream, ref in zip(streams, solo):
            assert _collect(got_stream) == ref
    finally:
        batcher.close()


# ------------------------------------------------------------------ continuous


def _collect(stream) -> List[int]:
    return [int(t) for chunk in stream for t in np.atleast_1d(chunk)]


def _solo_until_eos(gen, prompt, gid, prefix=None) -> List[int]:
    row = gen([prompt], constraint=gid, prefix=prefix)[0].tolist()
    out = []
    for t in row:
        out.append(t)
        if t == EOS:
            break
    return out


@pytest.mark.parametrize("sizes", [{}, {"block_size": 4}], ids=["one_block_a_row", "blocks_of_4"])
def test_continuous_constrained_streams_match_solo(tiny, cs, sizes):
    from unionml_tpu.serving import ContinuousBatcher

    module, params, _ = tiny
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=8, temperature=0.0, eos_id=EOS,
                         prompt_buckets=(8,), constraints=cs),
    )
    prompts = [[3, 14, 15], [7, 7, 9], [1, 2]]
    gids = [1, 2, 0]
    solo = [_solo_until_eos(gen, p, g) for p, g in zip(prompts, gids)]
    batcher = ContinuousBatcher(gen, slots=2, decode_chunk=2, **sizes)
    try:
        # more streams than slots: admission contention + slot reuse under
        # per-request grammars
        streams = [batcher.submit(p, constraint=g) for p, g in zip(prompts, gids)]
        for got_stream, ref in zip(streams, solo):
            assert _collect(got_stream) == ref
        # /metrics telemetry: one submission per grammar id recorded
        assert batcher.stats()["grammar_submissions"] == {0: 1, 1: 1, 2: 1}
    finally:
        batcher.close()


def test_continuous_constraint_survives_preemption(tiny, cs):
    """A preempted constrained request must resume masking at the DFA state its
    echo reached (the host walk in _admit_pending), not restart the grammar."""
    from unionml_tpu.serving import ContinuousBatcher

    module, params, _ = tiny
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=8, temperature=0.0, eos_id=EOS,
                         prompt_buckets=(8,), constraints=cs),
    )
    prompts = [[3, 14, 15], [7, 7, 9]]
    gids = [1, 2]
    solo = [_solo_until_eos(gen, p, g) for p, g in zip(prompts, gids)]
    # a pool sized for ONE worst-case request forces the second admission to
    # wait and residents to preempt under growth pressure
    batcher = ContinuousBatcher(gen, slots=2, decode_chunk=2, block_size=2, pool_blocks=9)
    try:
        streams = [batcher.submit(p, constraint=g) for p, g in zip(prompts, gids)]
        for got_stream, ref in zip(streams, solo):
            assert _collect(got_stream) == ref
    finally:
        batcher.close()


def test_continuous_engine_death_mid_admission_errors_the_stream(tiny):
    """A session popped from pending but not yet resident is reachable by
    NEITHER of the engine's death handlers — an engine-fatal crash during its
    admission must error its stream, not strand its consumer forever (found
    live: a constrained-draft prefill crash hung the submitting thread)."""
    from unionml_tpu.serving import ContinuousBatcher

    module, params, _ = tiny
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=4, temperature=0.0, eos_id=EOS, prompt_buckets=(8,)),
    )
    batcher = ContinuousBatcher(gen, slots=1)

    def boom(*a, **k):
        raise RuntimeError("injected engine-fatal admission failure")

    batcher._prefill_row = boom
    stream = batcher.submit([1, 2])
    with pytest.raises(RuntimeError, match="injected"):
        next(iter(stream))
    batcher.close()


def test_everything_composes_at_once(tiny, cs):
    """The capstone: int8 weights + int8 KV cache + paged block pool + shared
    system-prompt prefix + speculative decoding + per-request grammars, all in
    one continuously-batched engine — every concurrent stream token-exact
    against its solo run through the same maximal config."""
    from unionml_tpu.serving import ContinuousBatcher

    module, params, _ = tiny
    d_module, d_params = _draft_pair(tiny)
    gen = Generator(
        module, params,
        GenerationConfig(
            max_new_tokens=8, temperature=0.0, eos_id=EOS, prompt_buckets=(8,),
            kv_cache_dtype="int8", constraints=cs,
            draft=DraftSpec(module=d_module, params=d_params, gamma=2),
        ),
        quantize="int8",
    )
    prefix = gen.cache_prefix([11, 12, 13])
    prompts = [[3, 14, 15], [7, 7, 9], [1, 2]]
    gids = [1, 2, 0]
    solo = [_solo_until_eos(gen, p, g, prefix=prefix) for p, g in zip(prompts, gids)]
    batcher = ContinuousBatcher(gen, slots=2, decode_chunk=2, prefix=prefix, block_size=4)
    try:
        streams = [batcher.submit(p, constraint=g) for p, g in zip(prompts, gids)]
        for got_stream, ref, g in zip(streams, solo, gids):
            got = _collect(got_stream)
            assert got == ref, (g, got, ref)
            if g == 1:
                text = decode_text(got)
                assert re.fullmatch(r"[a-c]{3,5}", text) or (
                    len(text) < 3 and all(c in "abc" for c in text)
                ), text
    finally:
        batcher.close()


@pytest.mark.parametrize("seed", [42, 7, 1234])
def test_continuous_randomized_stress_matches_solo(tiny, cs, seed):
    """Seeded randomized stress: a dozen streams with random prompts, lengths,
    budgets, and grammar ids through a small paged pool (preemption-prone) —
    every stream token-exact against its solo (prompt, grammar, budget) run.
    Broadens the targeted oracles to arbitrary mixes (budget x grammar
    truncation, bucket variety, slot churn); three seeds soak different
    admission/preemption interleavings."""
    from unionml_tpu.serving import ContinuousBatcher

    module, params, _ = tiny
    rng = np.random.default_rng(seed)
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=8, temperature=0.0, eos_id=EOS,
                         prompt_buckets=(8,), constraints=cs),
    )
    jobs = []
    for _ in range(12):
        plen = int(rng.integers(1, 8))
        prompt = [int(t) for t in rng.integers(1, 40, size=plen)]
        gid = int(rng.integers(0, 3))
        budget = int(rng.integers(1, 9))
        jobs.append((prompt, gid, budget))

    # greedy truncation law: a budget-b run is the first b tokens of the
    # full-budget run (the budget only cuts the scan short), so one solo
    # generator + a slice serves every budget without extra compiles
    refs = [_solo_until_eos(gen, prompt, gid)[:budget] for prompt, gid, budget in jobs]
    batcher = ContinuousBatcher(gen, slots=3, decode_chunk=2, block_size=2, pool_blocks=9)
    try:
        streams = [
            batcher.submit(prompt, constraint=gid, max_new_tokens=budget)
            for prompt, gid, budget in jobs
        ]
        for i, (stream, ref) in enumerate(zip(streams, refs)):
            got = _collect(stream)
            assert got == ref, (i, jobs[i], got, ref)
        assert batcher.stats()["kv_blocks"]["used"] == 0  # allocator balanced
    finally:
        batcher.close()


def test_continuous_rejects_constraint_without_set(tiny):
    from unionml_tpu.serving import ContinuousBatcher

    module, params, _ = tiny
    gen = Generator(
        module, params,
        GenerationConfig(max_new_tokens=4, temperature=0.0, eos_id=EOS, prompt_buckets=(8,)),
    )
    batcher = ContinuousBatcher(gen, slots=1)
    try:
        with pytest.raises(ValueError, match="requires GenerationConfig.constraints"):
            batcher.submit([1, 2], constraint=1)
    finally:
        batcher.close()
