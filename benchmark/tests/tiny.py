"""Tiny presets of the cells for the CPU: the real cell and configuration
files with only sizes changed, handed to `run.run_cell` directly.

A tiny preset runs the bare float32 model (`device_type` cpu, so nothing
wraps it in bfloat16): its stated precision is float32, the step below is
bfloat16, and its limits are set here by the rules of PERF.md section 2
from readings on the CPU at these sizes (sound runs: under 1e-5 for the
critic; Grasp2Vec under 1e-5 on the first loss, 3e-3 on the gradient and
3e-2 on the change, its later losses chaotic under Adam; the bfloat16
control: 0.08 and 0.10 for the critic, 7e-4 on Grasp2Vec's first loss and
0.13 on its gradient). The cells' own limits come from the chip."""

import argparse

import manifest

TINY_MODEL = {
    "critic_c64": {
        "arguments": {"image_size": [96, 96], "num_convs": [2, 2, 1]},
        "model": {"image_size": [96, 96], "num_convs": [2, 2, 1]},
    },
    "grasp2vec_r50": {
        "arguments": {"scene_size": [64, 64], "goal_size": [64, 64], "resnet_size": 18},
        "model": {"image_size": [64, 64], "resnet_size": 18},
    },
}


TINY_LIMITS = {
    "critic_c64": {"loss1": 1e-3, "loss2": 1e-3, "loss3": 1e-3,
                   "grad_norm": 1e-3, "update_norm": 1e-3,
                   "parse_max_abs": 0, "decode_max_abs": 2},
    "grasp2vec_r50": {"loss1": 1e-4, "loss2": None, "loss3": None,
                      "grad_norm": 2e-2, "update_norm": 0.2},
}
TINY_CONTROL = "bfloat16"


def tiny_config(name):
    config = manifest.config(name)
    config["device_type"] = "cpu"
    config["control"] = [TINY_CONTROL]
    patch = TINY_MODEL[name]
    config["arguments"] = {**config.get("arguments", {}), **patch["arguments"]}
    config["model"] = {**config["model"], **patch["model"]}
    return config


def tiny_cell(name, batch=4, listed=True, **traffic):
    cell = manifest.cell(name, listed=listed)
    cell["batch"] = batch
    cell["warmup_steps"] = 4
    cell["trace_seconds"] = 0.5
    if cell["traffic"]["kind"] == "jpeg_records":
        traffic = {"records": 64, "base_images": 4, **traffic}
    cell["traffic"] = {**cell["traffic"], **traffic}
    cell["limits"] = dict(TINY_LIMITS[cell["config"]])
    return cell


def args(seed=3, seconds=1.0, trace=0):
    return argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
