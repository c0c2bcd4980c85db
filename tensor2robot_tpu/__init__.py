"""tensor2robot_tpu: a TPU-native (JAX/XLA/pjit/Pallas) rebuild of Tensor2Robot.

A spec-driven training/eval/export/inference framework for robotic perception
and control.  Models declare typed tensor specifications for their inputs; the
framework auto-generates the data-parsing pipeline, serving signatures, and
train/eval scaffolding from those specs.

Reference behavior: sarvex/tensor2robot (TF1 Estimator harness).  This package
is a from-scratch JAX design, not a port: models are pure functions over
pytrees, device placement is a `jax.sharding.Mesh`, collectives are XLA's, and
the hot ops compile through jit/pjit (with Pallas kernels where profitable).
"""

import time

#: When this process first touched the package, epoch nanoseconds: where
#: train/train_eval.py's `program.import` span starts.
IMPORT_START_NS = time.time_ns()

__version__ = "0.1.0"
