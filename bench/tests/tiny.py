"""Cells at a size the CPU runs in seconds: the same drivers, models and
comparisons as the chip's cells, at small widths."""

import time

import harness

OLMO = {"family": "olmo", "program_arch": "olmo-1b", "program_reduced": True,
        "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 256, "vocab_size": 128,
        "tie_word_embeddings": True, "rope_theta": 10000.0}
RESNET = {"family": "resnet18s", "width": 8, "classes": 10, "image": [32, 32, 3],
          "groupnorm_groups": 8}
CELLS = {
    "fold.olmo1b.silos16": (OLMO, {"driver": "fold", "pool": 4, "per_fold": 4, "draw": "rotate",
                                   "weights": {"kind": "lognormal", "sigma": 1.0, "mean": 1000},
                                   "sigma": 0.1, "warmup": 1, "check": 1, "check_within": 1}),
    "round.resnet18s.noniid": (RESNET, {"driver": "round", "samples": 2560, "classes": 10,
                                        "hw": [32, 32, 3], "noise": 2.0, "clients": 20,
                                        "classes_per_client": 2, "per_round": 2, "epochs": 5,
                                        "batch": 64, "lr": 0.001, "warmup": 2}),
    "decode.olmo1b.b8": (OLMO, {"driver": "decode", "batch": 2, "prompt": 8, "gen": 8,
                                "warmup": 1, "check": 1, "check_within": 1}),
}


def ctx(cell: str, seed: int = 2**33 + 3, seconds: float = 0.5):
    config, traffic = CELLS[cell]
    reg = harness.Registry()
    return harness.Ctx(cell=cell, config=config, traffic=traffic, model=reg.model(config),
                       seed=seed, seconds=seconds, trace=False, t_start=time.perf_counter(),
                       device_kind="TPU v5 lite")


def driver(cell: str):
    return harness.Registry().driver(CELLS[cell][1])


def correct(res: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in res["compared"].values())
CELLS["fold.resnet18s.c1000"] = (RESNET, {
    "driver": "fold", "pool": 24, "per_fold": 20, "draw": "sample",
    "weights": {"kind": "equal", "value": 500}, "sigma": 0.1, "warmup": 1, "check": 2,
    "check_within": 2})
