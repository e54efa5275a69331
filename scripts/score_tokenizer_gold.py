"""Score the built-in tokenize_ja lattice analyzer against the gold
segmentation fixture; prints one JSON line (the number docs/perf_history.md cites).

Run: python scripts/score_tokenizer_gold.py
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    from hivemall_tpu.nlp import tokenize_ja
    from hivemall_tpu.nlp.evaluate import load_gold, segmentation_prf
    from hivemall_tpu.nlp.tokenizer import backend_name

    data_dir = os.path.join(os.path.dirname(__file__), "..", "tests", "data")
    for tag, fname in (("dev", "tokenize_ja_gold.tsv"),
                       ("heldout", "tokenize_ja_heldout.tsv"),
                       ("blind2", "tokenize_ja_blind2.tsv"),
                       ("blind3", "tokenize_ja_blind3.tsv"),
                       ("blind4", "tokenize_ja_blind4.tsv"),
                       ("blind5", "tokenize_ja_blind5.tsv"),
                       ("blind6", "tokenize_ja_blind6.tsv")):
        gold = load_gold(os.path.join(data_dir, fname))
        pairs = [(toks, tokenize_ja(sent)) for sent, toks in gold]
        m = segmentation_prf(pairs)
        print(json.dumps({
            "metric": f"tokenize_ja_{tag}_f1",
            "value": round(m["f1"], 4),
            "unit": "span_f1",
            "precision": round(m["precision"], 4),
            "recall": round(m["recall"], 4),
            "sentences": len(gold),
            "gold_tokens": m["gold_tokens"],
            "backend": backend_name(),
        }))


if __name__ == "__main__":
    main()
