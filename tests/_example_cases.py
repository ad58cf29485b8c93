"""The example scripts every ``test_examples_<n>.py`` runs.

Every example script must run end-to-end (synthetic data, quick args).
Reference analogue: the train-tier tests (tests/python/train) that run
small full training loops and assert convergence — our examples embed
their own asserts, so a zero exit code means trained-and-checked.

One list, six test files: the driver's ``--dist loadfile`` pins a file
to one worker, and these 50 subprocess cases in one file were the
whole tail of the tier-1 run. The third field is the file a case runs
in, balanced on measured seconds per case (CHANGES.md, PR 21).
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CASES = [
    ("module/mnist_mlp.py", ["--epochs", "8"], 6),
    ("autograd/linear_regression.py", ["--iters", "60"], 6),
    ("image-classification/train_cifar10.py", [], 6),
    ("image-classification/train_imagenet.py",
     ["--benchmark", "1", "--num-layers", "18", "--batch-size", "8",
      "--iters", "2", "--image-shape", "64,64,3", "--num-classes", "10",
      "--dtype", "float32"], 3),
    ("image-classification/fine_tune.py", [], 6),
    ("rnn/lstm_bucketing.py", ["--epochs", "6"], 6),
    ("numpy-ops/custom_softmax.py", [], 3),
    ("torch/torch_module_mlp.py", [], 2),
    ("gan/dcgan.py", ["--iters", "120"], 2),
    ("autoencoder/autoencoder.py", [], 5),
    ("recommenders/matrix_fact.py", [], 2),
    ("multi-task/multitask_mlp.py", [], 1),
    ("adversary/fgsm.py", [], 4),
    ("svm/svm_toy.py", [], 4),
    ("rnn/bi_lstm_sort.py", [], 6),
    ("cnn_text/cnn_text_classification.py", [], 5),
    ("nce-loss/nce_word.py", [], 5),
    ("warpctc/lstm_ocr_toy.py", [], 4),
    ("reinforcement-learning/reinforce_chain.py", [], 5),
    ("model-parallel-lstm/model_parallel_lstm.py", ["--iters", "120"], 6),
    ("stochastic-depth/sd_resnet.py", ["--epochs", "30"], 1),
    ("neural-style/neural_style_toy.py", [], 3),
    ("dec/dec_toy.py", [], 2),
    ("speech/speech_gru_acoustic.py", ["--epochs", "10"], 1),
    ("speech/train_ctc.py",
     ["--config", "default.cfg", "test.wer_gate=0.2"], 4),
    ("bayesian-methods/sgld_regression.py", ["--iters", "6000"], 5),
    ("dsd/dsd_training.py", [], 1),
    ("sparse/linear_classification.py", [], 2),
    ("rcnn/proposal_demo.py", [], 1),
    ("memcost/inception_memcost.py", ["--batch-size", "1024"], 2),
    ("fcn-xs/fcn_toy.py", [], 3),
    ("ssd/multibox_toy.py", [], 2),
    ("captcha/captcha_ocr.py", [], 3),
    ("kaggle-ndsb1/train_plankton_style.py", ["--epochs", "8"], 4),
    ("rnn-time-major/lstm_time_major.py", ["--epochs", "12"], 2),
    ("notebooks/basics.py", [], 3),
    ("notebooks/composite_symbol.py", [], 5),
    ("notebooks/module_checkpointing.py", [], 1),
    ("ssd/train_ssd.py", ["--map-gate", "0.45"], 4),
    ("rcnn/train_rcnn.py",
     ["--map-gate", "0.45", "--ohem", "--scale-jitter", "--eval-scales",
      "64,96"], 1),
    ("rcnn/train_alternate.py", ["--map-gate", "0.4"], 3),
    ("rcnn/demo.py", [], 5),
    ("kaggle-ndsb2/train_ndsb2.py", [], 2),
    ("python-howto/debug_conv.py", [], 6),
    ("python-howto/multiple_outputs.py", [], 5),
    ("python-howto/monitor_weights.py", [], 4),
    ("python-howto/data_iter.py", [], 1),
    ("profiler/profile_training.py", ["--iters", "5"], 5),
    ("parallel/sequence_parallel_attention.py",
     ["--seq-len", "512", "--heads", "8", "--head-dim", "16"], 4),
    ("parallel/transformer_4d.py",
     ["--seq-len", "16", "--batch", "8", "--vocab", "64", "--d-model", "32",
      "--heads", "4", "--iters", "40"], 3),
]


def example_test(group):
    """The parametrised ``test_example_runs`` of one group's cases."""
    mine = [(script, extra) for script, extra, g in _CASES if g == group]

    @pytest.mark.parametrize("script,extra", mine,
                             ids=[script for script, _ in mine])
    def test_example_runs(script, extra, tmp_path):
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        res = subprocess.run(
            [sys.executable, os.path.join(ROOT, "examples", script)] + extra,
            capture_output=True, text=True, timeout=900, cwd=str(tmp_path),
            env=env)
        assert res.returncode == 0, (
            f"{script} failed\nstdout:\n{res.stdout[-3000:]}\n"
            f"stderr:\n{res.stderr[-3000:]}")

    return test_example_runs
