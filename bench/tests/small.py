"""Small sizes at which the benchmark's tests drive a cell on the CPU."""


def tweak(conf, cell):
    """The cell at base width 8, batch 16, 2 batches a client, 400 images:
    the same code paths as the chip's sizes."""
    conf = {**conf,
            "model": {**conf["model"], "base_filters": 8, "global_batch": 16},
            "override": {**conf["override"], "model.dcgan.base_filters": 8,
                         "shape.global_batch": 16},
            "data": {**conf["data"], "images": 400}}
    return conf, {**cell, "batches_per_client": 2}
