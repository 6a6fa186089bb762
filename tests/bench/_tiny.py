"""Tiny versions of the benchmark's cells for the CPU tests: the cell's
own files with the model cut to a few small layers and the traffic to a
few short requests. The reduction engine runs on the ``xla`` backend,
which needs no interpreter."""

from __future__ import annotations

import copy

from benchlib import common

TINY = dict(n_layers=2, d_model=64, n_heads=4, d_head=16, d_ff=128,
            vocab_size=256)


def train_cell():
    cell = dict(common.load("workloads", "olmo-1b.train.seq2048"),
                reduce_backend="xla")
    cfg = copy.deepcopy(common.load("configs", "olmo-1b"))
    cfg["model"].update(TINY, n_kv_heads=4)
    cfg["token_vocab"] = 250
    mix = dict(common.load("traffic", "train-seq2048"), seq=32, batch=2)
    return cell, cfg, mix


def serve_cell():
    cell = dict(common.load("workloads", "internlm2-1.8b.serve.chat"),
                reduce_backend="xla", slots=4, check_requests=4)
    cfg = copy.deepcopy(common.load("configs", "internlm2-1.8b"))
    cfg["model"].update(TINY, n_kv_heads=2)
    cfg["token_vocab"] = 256
    base = common.load("traffic", "chat-p512")
    mix = dict(base, prompt_len=16, rate_per_s=20.0,
               output=dict(base["output"], median=6, min=2, max=24))
    return cell, cfg, mix
