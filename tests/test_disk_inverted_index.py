"""Disk-backed inverted index (round-5 review next #9): the Lucene
role — persists across process restarts, scales past RAM, same surface
and numerics as the in-memory store."""

import math
import subprocess
import sys

import numpy as np
import pytest

from deeplearning4j_tpu.nlp.inverted_index import (
    DiskInvertedIndex,
    InvertedIndex,
)

DOCS = [
    ("the cat sat on the mat".split(), "a"),
    ("the dog sat".split(), "b"),
    ("cats and dogs".split(), None),
    ("mat and cat and mat".split(), "c"),
]


def _fill(idx):
    for toks, label in DOCS:
        idx.add_doc(toks, label=label)
    return idx


class TestDiskParity:
    """Every query must agree with the in-memory InvertedIndex."""

    def test_surface_parity(self, tmp_path):
        mem = _fill(InvertedIndex())
        with _fill(DiskInvertedIndex(str(tmp_path / "ix.db"))) as disk:
            assert disk.num_documents() == mem.num_documents()
            assert disk.vocab() == mem.vocab()
            for w in ("the", "cat", "sat", "ghost"):
                assert (disk.documents_containing(w)
                        == mem.documents_containing(w))
                assert (disk.document_frequency(w)
                        == mem.document_frequency(w))
            for i in range(len(DOCS)):
                assert disk.document(i) == mem.document(i)
                assert disk.label(i) == mem.label(i)
            for w in ("the", "cat", "mat"):
                for i in range(len(DOCS)):
                    assert disk.tfidf(w, i) == pytest.approx(
                        mem.tfidf(w, i))
            for q in (["cat", "mat"], ["dog"], ["ghost"], []):
                assert disk.search(q) == pytest.approx(mem.search(q))
            assert disk.all_documents() == mem.all_documents()

    def test_repeated_query_terms_match_memory_semantics(self, tmp_path):
        """Repeated query terms weight per occurrence in BOTH stores."""
        mem = _fill(InvertedIndex())
        with _fill(DiskInvertedIndex(str(tmp_path / "ix.db"))) as disk:
            q = ["cat", "cat", "mat"]
            assert disk.search(q) == pytest.approx(mem.search(q))

    def test_sample_batch(self, tmp_path):
        with _fill(DiskInvertedIndex(str(tmp_path / "ix.db"))) as disk:
            batch = disk.sample_batch(3, np.random.default_rng(0))
            assert len(batch) == 3

    def test_rejects_space_tokens(self, tmp_path):
        with DiskInvertedIndex(str(tmp_path / "ix.db")) as disk:
            with pytest.raises(ValueError, match="space"):
                disk.add_doc(["bad token"])

    def test_bulk_ingest_rolls_back_on_error(self, tmp_path):
        """A failed add_docs must leave NO partial rows behind — a
        later unrelated commit would otherwise persist them."""
        with DiskInvertedIndex(str(tmp_path / "ix.db")) as disk:
            disk.add_doc(["ok"])
            with pytest.raises(ValueError, match="space"):
                disk.add_docs([["fine"], ["also fine"], ["bad tok"]])
            disk.add_doc(["after"])  # commits; must not flush partials
            assert disk.num_documents() == 2
            assert disk.documents_containing("fine") == []


class TestPersistence:
    def test_survives_reopen(self, tmp_path):
        path = str(tmp_path / "ix.db")
        with _fill(DiskInvertedIndex(path)) as disk:
            want = disk.search(["cat", "mat"])
        with DiskInvertedIndex(path) as disk2:
            assert disk2.num_documents() == len(DOCS)
            assert disk2.search(["cat", "mat"]) == pytest.approx(want)
            # and keeps growing from where it left off
            new_id = disk2.add_doc("more cat content".split())
            assert new_id == len(DOCS)
            assert new_id in disk2.documents_containing("cat")

    def test_survives_process_restart(self, tmp_path):
        """The actual Lucene property: a DIFFERENT process reopens the
        index directory and reads the same postings."""
        path = str(tmp_path / "ix.db")
        with _fill(DiskInvertedIndex(path)) as disk:
            want_df = disk.document_frequency("the")
        code = (
            "import sys; sys.path.insert(0, %r)\n"
            "from deeplearning4j_tpu.nlp.inverted_index import "
            "DiskInvertedIndex\n"
            "with DiskInvertedIndex(%r) as ix:\n"
            "    print('DF', ix.document_frequency('the'),"
            " ix.num_documents())\n"
            % (sys.path[0] and __file__.rsplit('/tests', 1)[0], path))
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr
        assert f"DF {want_df} {len(DOCS)}" in p.stdout


class TestCorpusScale:
    def test_real_corpus_bulk_build_and_stream(self, tmp_path):
        """10k real sentences bulk-ingested in one transaction, then
        streamed back without materializing the corpus; TF-IDF search
        returns day-related sentences for a day query."""
        from deeplearning4j_tpu.datasets.fixtures import raw_sentences
        from deeplearning4j_tpu.nlp.tokenization import (
            DefaultTokenizerFactory,
        )

        tf = DefaultTokenizerFactory()
        sents = raw_sentences(limit=10_000)
        docs = (tf.create(s).get_tokens() for s in sents)
        with DiskInvertedIndex(str(tmp_path / "c.db")) as disk:
            n = disk.add_docs(docs)
            assert n == len(sents)
            assert disk.num_documents() == n
            assert disk.document_frequency("the") > 1000
            top = disk.search(["day", "night"], top_k=5)
            assert top and all(s > 0 for _, s in top)
            for doc_id, _ in top[:2]:
                text = disk.document(doc_id)
                assert "day" in text or "night" in text
            # streaming read touches every doc without a full list
            seen = sum(1 for _ in disk.iter_documents(batch_rows=1024))
            assert seen == n
            assert disk.size_bytes() > 100_000

    def test_math_matches_formula(self, tmp_path):
        with _fill(DiskInvertedIndex(str(tmp_path / "ix.db"))) as disk:
            # doc 3 = "mat and cat and mat": tf(mat)=2/5, df(mat)=2, N=4
            want = (2 / 5) * math.log(4 / 2)
            assert disk.tfidf("mat", 3) == pytest.approx(want)
