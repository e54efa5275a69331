"""Gold-standard segmentation accuracy gate for tokenize_ja.

Reference behavior bar: KuromojiUDF NORMAL mode over IPADic
(nlp/src/main/java/hivemall/nlp/tokenizer/KuromojiUDF.java:55-86). The
fixture is 100+ hand-verified everyday sentences segmented at IPADic
granularity (inflected predicates split stem + auxiliaries: 行きました ->
行き/まし/た; です/だ/ます conjugate as でし+た, だっ+た, ましょ+う).

Honesty note: the bundled lexicon was GROWN against this fixture
(dev-set methodology, VERDICT r3 next #4), so the measured score is an
upper bound on open-domain accuracy; the gate at F1 >= 0.9 is a
regression floor for lexicon/lattice/native-kernel changes, and
scripts/score_tokenizer_gold.py reports the current number for docs/perf_history.md."""

import os

import pytest

from hivemall_tpu.nlp import tokenize_ja
from hivemall_tpu.nlp.evaluate import (load_gold, segmentation_prf,
                                       token_spans)

GOLD_PATH = os.path.join(os.path.dirname(__file__), "data",
                         "tokenize_ja_gold.tsv")
HELDOUT_PATH = os.path.join(os.path.dirname(__file__), "data",
                            "tokenize_ja_heldout.tsv")


@pytest.fixture(scope="module")
def gold():
    fixture = load_gold(GOLD_PATH)
    assert len(fixture) >= 100
    return fixture


def test_gold_fixture_is_well_formed(gold):
    """Every gold line's tokens must tile the sentence minus punctuation/
    space (otherwise the span metric silently measures the wrong thing)."""
    for sent, toks in gold:
        stripped = "".join(ch for ch in sent
                           if ch not in "、。！？!?,. 　")
        assert "".join(toks) == stripped, sent


def test_normal_mode_f1_gate(gold):
    pairs = [(toks, tokenize_ja(sent)) for sent, toks in gold]
    m = segmentation_prf(pairs)
    assert m["f1"] >= 0.9, m
    assert m["precision"] >= 0.9, m
    assert m["recall"] >= 0.9, m


def test_heldout_f1_gate():
    """Second fixture, measured BLIND first (F1 0.872 before the vocabulary
    it exposed was added — the number docs/perf_history.md records as the open-domain
    estimate); after growth it joins the regression floor."""
    heldout = load_gold(HELDOUT_PATH)
    assert len(heldout) >= 30
    pairs = [(toks, tokenize_ja(sent)) for sent, toks in heldout]
    m = segmentation_prf(pairs)
    assert m["f1"] >= 0.9, m


def test_blind2_f1_gate():
    """Round-4b third fixture, measured BLIND first against the grown
    (3043-surface) lexicon: first-pass span F1 0.9773 — the number docs/perf_history.md
    records as the open-domain estimate for this lexicon generation (up
    from 0.872 for the previous one). After its three OOV misses (口座,
    毎週, について) were folded it joins the regression floor."""
    blind2 = load_gold(os.path.join(os.path.dirname(__file__), "data",
                                    "tokenize_ja_blind2.tsv"))
    assert len(blind2) >= 30
    pairs = [(toks, tokenize_ja(sent)) for sent, toks in blind2]
    m = segmentation_prf(pairs)
    assert m["f1"] >= 0.95, m


@pytest.mark.parametrize("fixture,first_pass", [
    ("tokenize_ja_blind3", 0.9320),
    ("tokenize_ja_blind4", 0.9328),
    ("tokenize_ja_blind5", 0.9522),
    ("tokenize_ja_blind6", 0.9310),
])
def test_round5_blind_f1_gates(fixture, first_pass):
    """Round-5 blind ladder (VERDICT r4 next #5). Three successive fixtures
    from OOV-dense domains (proper nouns, tech, business/law, medicine),
    each composed blind after the then-current lexicon froze:

    - blind3 first-pass 0.9320 — exposed the suffix-tier pricing bug (cheap
      single-kanji suffixes shredding unknown compounds: 減/税) AND the
      single-state-per-position Viterbi collapse (生ま/れ/た).
    - blind4 first-pass 0.9328 — after those fixes; exposed the unknown-
      model class: lexical-1-kanji + unknown-1-kanji undercutting the
      2-kanji unknown run (雪/崩, 法/案).
    - blind5 first-pass 0.9522 — after the kanji unknown retune
      ((900,900) -> (1100,500)); >= 0.95, the round-5 OOV-domain accuracy
      claim recorded in docs/perf_history.md. Each first-pass number was measured BEFORE
      any fix responding to that fixture; folds happened only after.

    blind6 (0.9310 first-pass, composed after the wave 2-5 vocabulary
    growth) found basic-verb inventory holes (溶かす/足す/渡る/~ておく) —
    the honest OOV-domain band across four blind fixtures is 0.93-0.95,
    each round's misses folded only after recording.

    Post-fold all four join the regression floor at >= 0.95."""
    fx = load_gold(os.path.join(os.path.dirname(__file__), "data",
                                f"{fixture}.tsv"))
    assert len(fx) >= 30
    pairs = [(toks, tokenize_ja(sent)) for sent, toks in fx]
    m = segmentation_prf(pairs)
    assert m["f1"] >= 0.95, m


def test_lexicon_scale():
    """Round-5 scale-up: 3043 -> ~15k surfaces (4.9x) over eighteen growth
    waves. Still ~4% of the reference's IPADic (KuromojiUDF.java:55-86) —
    the honest gap — but the blind ladder above measures what a user
    actually gets on OOV text."""
    from hivemall_tpu.nlp.lexicon_ja import build_lexicon

    assert len(build_lexicon()) >= 14500


def test_bulk_path_scores_identically(gold):
    """The native bulk Viterbi must score exactly like the per-text path
    on the whole fixture (segmentation parity at corpus scale)."""
    from hivemall_tpu.nlp import tokenize_ja_bulk

    sents = [s for s, _ in gold]
    bulk = tokenize_ja_bulk(sents)
    per_text = [tokenize_ja(s) for s in sents]
    assert bulk == per_text


def test_span_metric_sanity():
    assert token_spans(["ab", "c"]) == [(0, 2), (2, 3)]
    perfect = segmentation_prf([(["a", "bc"], ["a", "bc"])])
    assert perfect["f1"] == 1.0
    miss = segmentation_prf([(["a", "bc"], ["ab", "c"])])
    assert miss["f1"] == 0.0  # no span agrees
    half = segmentation_prf([(["a", "bc"], ["a", "b", "c"])])
    assert 0.0 < half["f1"] < 1.0
