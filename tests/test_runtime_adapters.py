"""Runtime (cluster/metrics), NLP, and DataFrame-adapter tests."""

import time

import numpy as np
import pytest

from hivemall_tpu.nlp import tokenize_ja, tokenize_ja_bulk
from hivemall_tpu.runtime import (Counter, MetricsRegistry, StopWatch,
                                  ThroughputCounter)
from hivemall_tpu.runtime.cluster import parse_mix_option


class TestRuntime:
    def test_stopwatch(self):
        sw = StopWatch("x")
        time.sleep(0.01)
        assert sw.elapsed() >= 0.009

    def test_counters(self):
        reg = MetricsRegistry()
        c = reg.counter("train", "iterations")
        c.increment()
        c.increment(4)
        assert reg.snapshot()["train.iterations"] == 5.0

    def test_throughput(self):
        t = ThroughputCounter(window_sec=10)
        for _ in range(100):
            t.record(10)
        assert t.last_reads_per_sec > 0

    def test_parse_mix_option(self):
        assert parse_mix_option("host1,host2") == ("host1", 11212)
        assert parse_mix_option("host1:9999") == ("host1", 9999)


class TestNlp:
    def test_tokenize_ja_basic(self):
        toks = tokenize_ja("日本語のテキストです")
        assert len(toks) >= 3
        assert all(t for t in toks)

    def test_tokenize_ja_stopwords(self):
        toks = tokenize_ja("日本語のテキスト", stopwords=["の"])
        assert "の" not in toks

    def test_tokenize_ja_modes(self):
        assert tokenize_ja("東京特許許可局", "search")  # decompounds long kanji runs
        with pytest.raises(ValueError):
            tokenize_ja("x", "bogus")

    def test_tokenize_ja_mixed_scripts(self):
        toks = tokenize_ja("JAXで機械学習2026")
        assert any("JAX" in t for t in toks)

    def test_tokenize_ja_is_morphological_not_charclass(self):
        """The in-image default backend must segment morphologically
        (KuromojiUDF NORMAL parity target): これはペンです contains the
        hiragana run これはです-pieces that a character-class splitter can
        only emit fused (これは / です), while a morphological analyzer
        separates the pronoun from the topic particle."""
        from hivemall_tpu.nlp.tokenizer import _charclass_tokenize, backend_name

        assert backend_name() in ("lattice", "fugashi", "janome")
        toks = tokenize_ja("これはペンです")
        assert toks == ["これ", "は", "ペン", "です"], toks
        # the charclass fallback provably cannot do this: it fuses the
        # pronoun with the topic particle (one hiragana run)
        assert _charclass_tokenize("これはペンです")[0] == "これは"

        toks = tokenize_ja("東京で寿司を食べた")
        assert toks == ["東京", "で", "寿司", "を", "食べ", "た"], toks
        # charclass fuses the verb stem's kanji with the auxiliary kana
        assert "食べ" not in _charclass_tokenize("東京で寿司を食べた")

    def test_tokenize_ja_ipadic_granularity(self):
        """Inflected predicates split stem + auxiliaries like IPADic
        (読みました -> 読み/まし/た)."""
        toks = tokenize_ja("彼女は新しい本を読みました")
        assert toks == ["彼女", "は", "新しい", "本", "を", "読み", "まし",
                        "た"], toks

    def test_tokenize_ja_search_mode_dictionary_decompound(self):
        """SEARCH mode emits a long compound's dictionary-backed parts
        (Kuromoji search-mode analog); all-unknown compounds fall back to
        recall-oriented 2-grams rather than an arbitrary lattice split."""
        from hivemall_tpu.nlp.lattice import LatticeTokenizer

        t = LatticeTokenizer()
        assert t.decompound("関西国際空港") == ["関西", "国際", "空港"]
        # all-unknown compound: no dictionary backing -> no lattice split
        assert t.decompound("特許許可局") == []
        # SEARCH keeps the 2-gram fallback for those
        toks = tokenize_ja("東京特許許可局", "search")
        assert "特許" in toks and "許可" in toks

    def test_tokenize_ja_stoptags_filter_pos(self):
        """POS stoptags drop particles/auxiliaries (the classic Kuromoji
        stoptag use), keeping content morphemes."""
        toks = tokenize_ja("私は日本語を勉強しています", "normal", None,
                           ["助詞", "助動詞"])
        assert "は" not in toks and "を" not in toks and "ます" not in toks
        assert "私" in toks and "日本語" in toks and "勉強" in toks


class TestAdapters:
    def _df(self):
        import pandas as pd

        rng = np.random.RandomState(0)
        n, d = 200, 8
        w = rng.randn(d)
        X = rng.randn(n, d).astype(np.float32)
        y = np.sign(X @ w)
        feats = [[f"{i}:{X[r, i]}" for i in range(d)] for r in range(n)]
        return pd.DataFrame({"features": feats, "label": y})

    def test_train_via_dataframe(self):
        from hivemall_tpu.adapters import hivemall_ops

        hf = hivemall_ops(self._df())
        model = hf.train_arow("features", "label", "-dims 64")
        scores = model.predict(self._df()["features"].tolist())
        acc = np.mean(np.sign(scores) == self._df()["label"].to_numpy())
        assert acc > 0.9

    def test_amplify(self):
        from hivemall_tpu.adapters import hivemall_ops

        hf = hivemall_ops(self._df())
        assert len(hf.amplify(3).df) == 600

    def test_grouped_argmin_kld(self):
        import pandas as pd

        from hivemall_tpu.adapters import hivemall_ops

        df = pd.DataFrame({"feature": ["a", "a", "b"],
                           "weight": [1.0, 3.0, 5.0],
                           "covar": [0.01, 1.0, 1.0]})
        out = hivemall_ops(df).groupby("feature").argmin_kld("weight", "covar")
        a_val = float(out[out["feature"] == "a"]["value"].iloc[0])
        assert a_val == pytest.approx((1 / 0.01 + 3) / (1 / 0.01 + 1))

    def test_predict_stream(self):
        from hivemall_tpu.adapters import hivemall_ops
        from hivemall_tpu.adapters.dataframe import predict_stream

        df = self._df()
        model = hivemall_ops(df).train_perceptron("features", "label", "-dims 64")
        batches = [df.iloc[:50], df.iloc[50:100]]
        outs = list(predict_stream(model, batches))
        assert len(outs) == 2 and len(outs[0]) == 50

    def test_part_amplify_and_explode_array(self):
        import pandas as pd

        from hivemall_tpu.adapters import hivemall_ops

        hf = hivemall_ops(self._df())
        assert len(hf.part_amplify(2).df) == 400
        df = pd.DataFrame({"id": [1, 2], "arr": [[10, 20], [30]]})
        out = hivemall_ops(df).explode_array("arr").df
        assert out["arr"].tolist() == [10, 20, 30]

    def test_minhash_dsl(self):
        import pandas as pd

        from hivemall_tpu.adapters import hivemall_ops
        from hivemall_tpu.knn import minhashes

        df = pd.DataFrame({"item": [7], "features": [["a:1", "b:1"]]})
        out = hivemall_ops(df).minhash("item", "features").df
        assert out["item"].tolist() == [7] * 5  # one row per hash function
        assert out["clusterid"].tolist() == minhashes(["a:1", "b:1"])

    def test_quantify_dsl(self):
        import pandas as pd

        from hivemall_tpu.adapters import hivemall_ops

        df = pd.DataFrame({"color": ["red", "blue", "red"], "n": [3, 1, 2]})
        out = hivemall_ops(df).quantify("color", "n").df
        assert out["color"].tolist() == [0.0, 1.0, 0.0]  # first-seen ids
        assert out["n"].tolist() == [3.0, 1.0, 2.0]  # numerics pass through

    def test_binarize_label_dsl(self):
        import pandas as pd

        from hivemall_tpu.adapters import hivemall_ops

        df = pd.DataFrame({"pos": [2, 0], "neg": [1, 1],
                           "features": [["a:1"], ["b:1"]]})
        out = hivemall_ops(df).binarize_label("pos", "neg", "features").df
        assert out["label"].tolist() == [1, 1, 0, 0]
        assert out["features"].iloc[3] == ["b:1"]

    def test_lr_datagen_frame_and_set_mix_servs(self):
        from hivemall_tpu.adapters import hivemall_ops
        from hivemall_tpu.adapters.dataframe import lr_datagen_frame

        df = lr_datagen_frame("-n_examples 120 -n_features 5 -n_dims 32 -cl")
        assert len(df) == 120 and set(df["label"]) <= {0.0, 1.0}
        # -mix injection parses through every trainer's options and, since
        # PR 28, means what it says: one replica a local device (the tests'
        # eight), mixed into one model
        hf = hivemall_ops(df).set_mix_servs("host1,host2")
        model = hf.train_perceptron("features", "label",
                                    "-dims 32 -mini_batch 8")
        assert model.predict(df["features"].tolist()).shape == (120,)
        assert int(model.state.step) == 120

    def test_injected_mix_with_the_exact_scan_is_refused_in_words(self):
        import pytest

        from hivemall_tpu.adapters import hivemall_ops
        from hivemall_tpu.adapters.dataframe import lr_datagen_frame

        df = lr_datagen_frame("-n_examples 40 -n_features 5 -n_dims 32 -cl")
        hf = hivemall_ops(df).set_mix_servs("host1,host2")
        with pytest.raises(ValueError, match="-mix on 8 devices needs "
                                             "-mini_batch B > 1"):
            hf.train_perceptron("features", "label", "-dims 32")


class TestTokenizeJaExtended:
    def test_extended_unigrams_unknown_words(self):
        """EXTENDED replaces unknown (OOV) tokens with character 1-grams
        (Kuromoji Mode.EXTENDED semantics); known dictionary words pass
        through whole."""
        from hivemall_tpu.nlp.tokenizer import backend_name

        toks = tokenize_ja("ガラパゴスのペン", "extended")
        if backend_name() != "lattice":
            return  # membership heuristic differs on external backends
        # ガラパゴス is OOV -> unigrammed; ペン is a lexicon word -> whole
        for ch in "ガラパゴス":
            assert ch in toks, toks
        assert "ガラパゴス" not in toks, toks
        assert "ペン" in toks, toks

    def test_extended_differs_from_search(self):
        text = "ガラパゴス諸島"
        assert tokenize_ja(text, "search") != tokenize_ja(text, "extended")

    def test_search_keeps_unknowns_whole(self):
        toks = tokenize_ja("ガラパゴス", "search")
        assert "ガラパゴス" in toks


class TestNativeLatticeBulk:
    def test_bulk_parity_with_per_text(self):
        """Native bulk Viterbi must segment EXACTLY like the Python lattice
        (same candidate order -> same tie-breaks); randomized corpus."""
        import random

        from hivemall_tpu.nlp.lattice import LatticeTokenizer
        from hivemall_tpu.nlp.lexicon_ja import build_lexicon

        rng = random.Random(7)
        words = list(build_lexicon())
        kanji = [chr(c) for c in range(0x4E00, 0x4E40)]
        kata = [chr(c) for c in range(0x30A1, 0x30E0)]

        def text():
            parts = []
            for _ in range(rng.randint(1, 15)):
                r = rng.random()
                if r < 0.5:
                    parts.append(rng.choice(words))
                elif r < 0.7:
                    parts.append("".join(rng.choice(kanji)
                                         for _ in range(rng.randint(1, 6))))
                elif r < 0.85:
                    parts.append("".join(rng.choice(kata)
                                         for _ in range(rng.randint(1, 7))))
                else:
                    parts.append(rng.choice(["、", "。", " ", "12", "ab"]))
            return "".join(parts)

        texts = [text() for _ in range(200)]
        lt = LatticeTokenizer()
        # call the native path directly so a missing .so/symbol registers
        # as a SKIP, never as a vacuous Python-vs-Python pass
        bulk = lt._tokenize_bulk_native(texts)
        if bulk is None:
            import pytest

            pytest.skip("native lattice kernel unavailable")
        per = [lt.tokenize(t) for t in texts]
        assert bulk == per

    def test_tokenize_ja_bulk_matches_per_text(self):
        texts = ["これはペンです", "東京で寿司を食べた。", "",
                 "機械学習のテキスト分類"]
        bulk = tokenize_ja_bulk(texts, stoptags=["助詞"])
        per = [tokenize_ja(t, stoptags=["助詞"]) for t in texts]
        assert bulk == per

    def test_tokenize_ja_bulk_other_modes_fall_back(self):
        texts = ["東京特許許可局"]
        assert tokenize_ja_bulk(texts, "search") == \
            [tokenize_ja(texts[0], "search")]
