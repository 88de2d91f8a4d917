"""Training data pipeline: tokenize/pack determinism, dp sharding,
end-to-end train-on-a-text-file with checkpoint resume (VERDICT r3
task 9; reference counterpart: recipe-level HF-datasets pipelines,
``llm/llama-3_1-finetuning/lora.yaml``)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from skypilot_tpu.train.data import TokenStream, packed_batches

_CORPUS = ("the quick brown fox jumps over the lazy dog. " * 200 +
           "pack my box with five dozen liquor jugs. " * 200)


@pytest.fixture(scope='module')
def corpus_file(tmp_path_factory):
    p = tmp_path_factory.mktemp('corpus') / 'corpus.txt'
    p.write_text(_CORPUS)
    return str(p)


class TestPacking:

    def test_shapes_and_shift(self, corpus_file):
        stream = TokenStream(corpus_file)
        it = packed_batches(stream, batch=4, seq=32)
        b = next(it)
        assert b['inputs'].shape == (4, 32)
        assert b['targets'].shape == (4, 32)
        # next-token objective: targets are inputs shifted by one
        np.testing.assert_array_equal(b['inputs'][:, 1:],
                                      b['targets'][:, :-1])

    def test_deterministic_and_resumable(self, corpus_file):
        stream = TokenStream(corpus_file)
        full = [next(packed_batches(stream, batch=2, seq=16,
                                    start_step=s))
                for s in range(5)]
        it = packed_batches(stream, batch=2, seq=16)
        seq = [next(it) for _ in range(5)]
        for a, b in zip(full, seq):
            np.testing.assert_array_equal(a['inputs'], b['inputs'])

    def test_dp_ranks_disjoint(self, corpus_file):
        stream = TokenStream(corpus_file)
        b0 = next(packed_batches(stream, batch=2, seq=16, dp_rank=0,
                                 dp_size=2))
        b1 = next(packed_batches(stream, batch=2, seq=16, dp_rank=1,
                                 dp_size=2))
        assert not np.array_equal(b0['inputs'], b1['inputs'])
        # rank 1 step 0 reads the window right after rank 0's rows
        stream2 = TokenStream(corpus_file)
        g = next(packed_batches(stream2, batch=4, seq=16))
        np.testing.assert_array_equal(g['inputs'][:2], b0['inputs'])
        np.testing.assert_array_equal(g['inputs'][2:], b1['inputs'])

    def test_dir_and_glob_sources(self, tmp_path):
        (tmp_path / 'a.txt').write_text('aaaa ' * 50)
        (tmp_path / 'b.txt').write_text('bbbb ' * 50)
        s = TokenStream(str(tmp_path))
        assert len(s) > 100
        s2 = TokenStream(str(tmp_path / '*.txt'))
        assert len(s2) == len(s)

    def test_too_small_corpus_rejected(self, tmp_path):
        p = tmp_path / 'tiny.txt'
        p.write_text('hi')
        stream = TokenStream(str(p))
        with pytest.raises(ValueError, match='need >= seq\\+2'):
            next(packed_batches(stream, batch=1, seq=512))


@pytest.mark.slow
class TestTrainCli:

    def _run(self, args, cwd):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=repo)
        return subprocess.run(
            [sys.executable, '-m', 'skypilot_tpu.train'] + args,
            capture_output=True, text=True, cwd=cwd, env=env, check=False)

    def test_loss_decreases_and_resumes(self, corpus_file, tmp_path):
        """Train tiny model on a text file: loss decreases; a second
        invocation resumes from the checkpoint and continues to the step
        target (exactly-once: total steps match, data position follows
        the restored step)."""
        ckpt = str(tmp_path / 'ckpt')
        base = ['--model', 'tiny', '--data', corpus_file, '--batch', '8',
                '--seq', '64', '--lr', '1e-2', '--warmup-steps', '2',
                '--log-every', '2', '--ckpt-dir', ckpt]
        r1 = self._run(base + ['--steps', '6', '--save-every', '100'],
                       str(tmp_path))
        assert r1.returncode == 0, r1.stderr[-2000:]
        losses = [json.loads(l)['loss'] for l in r1.stdout.splitlines()
                  if l.startswith('{')]
        assert len(losses) >= 3
        assert losses[-1] < losses[0], losses
        assert os.path.exists(os.path.join(ckpt, 'LATEST'))

        # resume: step target extended; must continue from step 6
        r2 = self._run(base + ['--steps', '8'], str(tmp_path))
        assert r2.returncode == 0, r2.stderr[-2000:]
        assert 'resumed' in r2.stdout and 'step 6' in r2.stdout, r2.stdout
        assert 'done at step 8' in r2.stdout, r2.stdout
