"""Trainer CLI: parse configs/bindings, run train_eval_model.

Usage (reference bin/run_t2r_trainer.py:29-37 parity):
  python -m tensor2robot_tpu.bin.run_t2r_trainer \
      --gin_configs=path/to/config.gin \
      --gin_bindings="train_eval_model.max_train_steps = 1000"
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
    train_eval_model = cfg.get_configurable("train_eval_model")
    train_eval_model()


if __name__ == "__main__":
    app.run(main)
