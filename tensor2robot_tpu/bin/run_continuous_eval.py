"""Continuous-eval CLI: a standalone eval job tailing a trainer's model_dir.

The eval half of the learner/eval process topology (reference README:44-51;
"continuous_eval" mode of utils/train_eval.py:584-610):

  python -m tensor2robot_tpu.bin.run_continuous_eval \
      --gin_configs=path/to/config.gin \
      --gin_bindings="continuous_eval.model_dir = '/tmp/run'"
"""

from __future__ import annotations

from absl import app, flags

FLAGS = flags.FLAGS
flags.DEFINE_multi_string(
    "gin_configs", [], "Paths to config files applied in order."
)
flags.DEFINE_multi_string(
    "gin_bindings", [], "Individual bindings applied after config files."
)


def main(argv):
    del argv
    import tensor2robot_tpu.config.defaults  # registers the surface

    from tensor2robot_tpu import config as cfg
    from tensor2robot_tpu.utils.compile_cache import enable_compile_cache

    # Before the first compile: a cold process re-running the same step
    # reads it back instead of recompiling (utils/compile_cache.py).
    enable_compile_cache()
    cfg.parse_config_files_and_bindings(FLAGS.gin_configs, FLAGS.gin_bindings)
    continuous_eval = cfg.get_configurable("continuous_eval")
    continuous_eval()


if __name__ == "__main__":
    app.run(main)
