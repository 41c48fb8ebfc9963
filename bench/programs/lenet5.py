"""The program's LeNet-5 (``repro.models.paper_models``) built from a
configuration file."""
from __future__ import annotations


def task(c: dict):
    from repro.fl.task import vision_task
    return vision_task("lenet5", n_classes=c["n_classes"],
                       in_ch=c["in_channels"])
