"""inference.Translator / inference.Classifier: raw-input prediction over
trained models, with save/load round-trips — the deployment story the
reference lacks (it trains and discards, quirk Q7 / SURVEY.md §5)."""

import jax
import numpy as np
import pytest

from machine_learning_apache_spark_tpu.inference import Classifier, Translator
from machine_learning_apache_spark_tpu.recipes.translation import train_translator


@pytest.fixture(scope="module")
def trained():
    """A translator trained well on the deterministic word→word synthetic
    task (each source word maps to exactly one target word)."""
    out = train_translator(
        epochs=6, synthetic_n=1024, batch_size=16, max_len=10,
        d_model=64, ffn_hidden=128, num_heads=4, dropout=0.0, log_every=0,
        use_mesh=False, seed=7,
        _return_translator=True,
    )
    return out["translator"], out


class TestTranslator:
    def test_translates_strings(self, trained):
        t, _ = trained
        from machine_learning_apache_spark_tpu.data.datasets import (
            synthetic_translation_pairs,
        )

        pairs = synthetic_translation_pairs(1024, min_len=3, max_len=6, seed=7)
        srcs = [s for s, _ in pairs[:8]]
        refs = [r for _, r in pairs[:8]]
        hyps = t(srcs)
        assert len(hyps) == 8 and all(isinstance(h, str) for h in hyps)
        # deterministic word-for-word task: a well-trained model emits the
        # exact target words for most positions
        correct = total = 0
        for hyp, ref in zip(hyps, refs):
            h, r = hyp.split(), ref.split()
            total += len(r)
            correct += sum(a == b for a, b in zip(h, r))
        assert correct / total > 0.6, (correct, total, hyps[:2], refs[:2])

    def test_methods_agree_on_shapes(self, trained):
        t, _ = trained
        srcs = ["one two three"]
        for method, kw in [
            ("greedy", {}),
            ("beam", {"beam_size": 3}),
            ("sample", {"temperature": 0.5, "top_k": 5, "rng": jax.random.key(0)}),
        ]:
            out = t(srcs, method=method, **kw)
            assert len(out) == 1 and isinstance(out[0], str)
        with pytest.raises(ValueError, match="method"):
            t(srcs, method="nope")
        with pytest.raises(ValueError, match="rng"):
            t(srcs, method="sample")  # silent fixed default would repeat

    def test_translator_beam_matches_beam_translate(self, trained):
        """``__call__(method="beam")`` is the home of beam search (the
        serving engine decodes greedily only): its strings are those of
        ``beam_translate`` called directly on the same ids, and one beam
        is the greedy decoder."""
        from machine_learning_apache_spark_tpu.data.text import EOS_ID, SOS_ID
        from machine_learning_apache_spark_tpu.models import beam_translate
        from machine_learning_apache_spark_tpu.train.metrics import (
            strip_special_ids,
        )

        t, _ = trained
        texts = ["one two three", "alpha beta gamma delta", "epsilon"]
        ys = beam_translate(
            t.model, t.params, np.asarray(t.src_pipe(texts)),
            beam_size=2, max_new_tokens=4, sos_id=SOS_ID, eos_id=EOS_ID,
        )
        rows = strip_special_ids(
            ys, pad_id=t.model.cfg.pad_id, sos_id=SOS_ID, eos_id=EOS_ID
        )
        direct = [" ".join(t.trg_pipe.vocab.lookup_tokens(r)) for r in rows]
        assert t(texts, method="beam", beam_size=2, max_new_tokens=4) == direct
        assert t(
            texts, method="beam", beam_size=1, max_new_tokens=4
        ) == t(texts, method="greedy", max_new_tokens=4)

    def test_unregistered_tokenizer_fails_at_save(self, trained, tmp_path):
        """A pipeline built around a bare callable cannot be rebuilt by
        load(); save() must refuse up front, not persist an unloadable
        model."""
        from machine_learning_apache_spark_tpu.data.text import TextPipeline

        t, _ = trained
        broken = Translator(
            t.model, t.params,
            TextPipeline(t.src_pipe.vocab, lambda s: s.split(), max_seq_len=9),
            t.trg_pipe,
        )
        with pytest.raises(ValueError, match="not a registered name"):
            broken.save(str(tmp_path / "broken"))

    def test_save_load_round_trip(self, trained, tmp_path):
        t, _ = trained
        srcs = ["alpha beta gamma", "delta epsilon"]
        before = t(srcs)
        t.save(str(tmp_path / "model"))
        t2 = Translator.load(str(tmp_path / "model"))
        after = t2(srcs)
        assert before == after
        # vocab round-trips exactly, specials included
        assert t2.trg_pipe.vocab.itos == t.trg_pipe.vocab.itos
        assert t2.src_pipe.vocab["<unk>"] == t.src_pipe.vocab["<unk>"]
        # re-save over the same directory is a clean overwrite
        t2.save(str(tmp_path / "model"))
        assert Translator.load(str(tmp_path / "model"))(srcs) == before

    def test_shadowing_custom_tokenizer_refused(self, trained, tmp_path):
        """A custom callable whose __name__ collides with a registry key
        must not be silently swapped for the built-in on load."""
        from machine_learning_apache_spark_tpu.data.text import TextPipeline

        t, _ = trained

        def word_punct(s):  # shadows the registry name
            return s.split()

        broken = Translator(
            t.model, t.params,
            TextPipeline(t.src_pipe.vocab, word_punct, max_seq_len=9),
            t.trg_pipe,
        )
        with pytest.raises(ValueError, match="different callable"):
            broken.save(str(tmp_path / "shadow"))


class TestClassifier:
    def test_mlp_predict_and_round_trip(self, tmp_path):
        from machine_learning_apache_spark_tpu.data.datasets import (
            synthetic_multiclass,
        )
        from machine_learning_apache_spark_tpu.recipes.mlp import train_mlp

        # the sigmoid MLP at SGD(0.03) learns slowly: the known-good recipe
        # config (cf. TestMLPRecipe) reaches >55% at 250 epochs
        out = train_mlp(
            epochs=250, synthetic_n=480, batch_size=8, _return_classifier=True
        )
        clf = out["classifier"]
        frame = synthetic_multiclass(480, num_features=4, num_classes=3, seed=1234)
        feats, labels = frame.arrays()
        preds = np.asarray(clf.predict(feats))
        acc = (preds == np.asarray(labels)).mean() * 100
        # the classifier must track the recipe's own reported accuracy
        assert acc > out["accuracy"] - 10.0, (acc, out["accuracy"])
        assert acc > 50.0, acc
        probs = np.asarray(clf.predict_proba(feats[:5]))
        np.testing.assert_allclose(probs.sum(axis=-1), 1.0, rtol=1e-5)

        clf.save(str(tmp_path / "mlp"))
        clf2 = Classifier.load(str(tmp_path / "mlp"))
        np.testing.assert_array_equal(
            np.asarray(clf2.predict(feats[:20])), preds[:20]
        )

    def test_lstm_predicts_raw_strings(self, tmp_path):
        from machine_learning_apache_spark_tpu.data.datasets import (
            synthetic_text_classification,
        )
        from machine_learning_apache_spark_tpu.recipes.lstm import train_lstm

        out = train_lstm(
            epochs=2, synthetic_n=512, batch_size=16, max_seq_len=24,
            _return_classifier=True,
        )
        clf = out["classifier"]
        texts, labels = synthetic_text_classification(64, num_classes=4, seed=0)
        preds = np.asarray(clf.predict(texts))  # raw strings in
        assert preds.shape == (64,)
        acc = (preds == np.asarray(labels)).mean() * 100
        assert acc > 30.0, acc  # beats 4-class chance

        clf.save(str(tmp_path / "lstm"))
        clf2 = Classifier.load(str(tmp_path / "lstm"))
        np.testing.assert_array_equal(
            np.asarray(clf2.predict(texts[:10])), preds[:10]
        )
        assert clf2.last_timestep and clf2.pipeline is not None

    def test_cnn_classifier_batched(self):
        from machine_learning_apache_spark_tpu.recipes.cnn import train_cnn

        out = train_cnn(
            epochs=1, synthetic_n=256, batch_size=16, hidden_units=4,
            _return_classifier=True,
        )
        clf = out["classifier"]
        clf.batch_size = 100  # forces a ragged chunked predict
        x = np.random.default_rng(0).normal(size=(256, 28, 28, 1)).astype("float32")
        assert np.asarray(clf.predict(x)).shape == (256,)


class TestRegisteredCustomTokenizer:
    def test_registered_custom_tokenizer_persists(self, trained, tmp_path):
        """register_tokenizer closes the loop the save() errors point to: a
        custom tokenizer registered under its own name saves and loads."""
        from machine_learning_apache_spark_tpu.data.text import (
            TextPipeline,
            register_tokenizer,
        )

        def upper_split(s):
            return s.upper().split()

        register_tokenizer("upper_split_test", upper_split)
        try:
            t, _ = trained
            custom = Translator(
                t.model, t.params,
                TextPipeline(
                    t.src_pipe.vocab, "upper_split_test", max_seq_len=9,
                    fixed_len=10,
                ),
                t.trg_pipe,
            )
            custom.save(str(tmp_path / "custom"))
            loaded = Translator.load(str(tmp_path / "custom"))
            assert loaded.src_pipe.tokenizer is upper_split
            assert loaded(["a b"]) == custom(["a b"])
        finally:
            from machine_learning_apache_spark_tpu.data import text

            text._TOKENIZERS.pop("upper_split_test", None)

    def test_shadowing_builtin_requires_overwrite(self):
        import pytest as _pytest

        from machine_learning_apache_spark_tpu.data.text import (
            register_tokenizer,
        )

        with _pytest.raises(ValueError, match="already registered"):
            register_tokenizer("word_punct", lambda s: s.split())
        with _pytest.raises(TypeError, match="callable"):
            register_tokenizer("not_fn", 42)

    def test_custom_tokenizer_fresh_process_round_trip(self, trained, tmp_path):
        """The full spacy-seam contract (``pytorch_machine_translator.py:20-21``):
        a custom tokenizer registered under its own name → ``save`` → a FRESH
        python process re-registers the name, ``load``s, and produces
        identical translations. Same-process reload (above) can hide registry
        state leaking through module globals; a subprocess cannot."""
        import json as _json
        import os
        import subprocess
        import sys

        from machine_learning_apache_spark_tpu.data.text import (
            TextPipeline,
            register_tokenizer,
        )

        def upper_split(s):
            return s.upper().split()

        register_tokenizer("upper_split_fresh", upper_split)
        try:
            t, _ = trained
            custom = Translator(
                t.model, t.params,
                TextPipeline(
                    t.src_pipe.vocab, "upper_split_fresh", max_seq_len=9,
                    fixed_len=10,
                ),
                t.trg_pipe,
            )
            model_dir = str(tmp_path / "fresh")
            custom.save(model_dir)
            srcs = ["alpha beta gamma", "delta epsilon"]
            before = custom(srcs)

            repo_root = os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))
            )
            env = {
                **os.environ,
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": repo_root
                + os.pathsep
                + os.environ.get("PYTHONPATH", ""),
            }
            child = f"""
import json
from machine_learning_apache_spark_tpu.data.text import register_tokenizer
from machine_learning_apache_spark_tpu.inference import Translator

def upper_split(s):
    return s.upper().split()

register_tokenizer("upper_split_fresh", upper_split)
loaded = Translator.load({model_dir!r})
assert loaded.src_pipe.tokenizer is upper_split
print("RESULT:" + json.dumps(loaded({srcs!r})))
"""
            proc = subprocess.run(
                [sys.executable, "-c", child],
                capture_output=True, text=True, timeout=600,
                cwd=str(tmp_path), env=env,
            )
            assert proc.returncode == 0, proc.stderr[-2000:]
            line = [
                l for l in proc.stdout.splitlines() if l.startswith("RESULT:")
            ][0]
            assert _json.loads(line[len("RESULT:"):]) == before

            # Without the re-registration the load must fail loudly (the
            # recorded name cannot resolve), not silently mistokenize.
            bad = subprocess.run(
                [
                    sys.executable, "-c",
                    "from machine_learning_apache_spark_tpu.inference "
                    "import Translator\n"
                    f"Translator.load({model_dir!r})",
                ],
                capture_output=True, text=True, timeout=600,
                cwd=str(tmp_path), env=env,
            )
            assert bad.returncode != 0
            assert "upper_split_fresh" in bad.stderr
        finally:
            from machine_learning_apache_spark_tpu.data import text

            text._TOKENIZERS.pop("upper_split_fresh", None)
