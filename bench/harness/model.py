"""The system under test, built as a deployment would build it.

The configuration file names the program's registry arch and gives every size
it runs with; keys that differ from the registry entry (a cut in depth) replace
it. Weights come from ``weights.make`` (the benchmark's own draw from the
seed, in the layout of the configuration's model module); calibration and
post-training quantization to the int8 tree are the program's own, on the
device, after which the bfloat16 tree is dropped.
"""
from __future__ import annotations

import dataclasses
import gc

from bench.harness import weights


def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    from repro.configs import get

    base = get(c["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    over = {k: v for k, v in c.items() if k in fields and k != "name"}
    return dataclasses.replace(base, **over)


def build(c: dict, layout: dict, seed: int, marks: dict, clock):
    """The int8 tree for ``seed``, drawn in ``layout`` (the model module's);
    ``marks`` receives the seconds (and compile seconds) of each part."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.core import calibration, qlinear as ql
    from repro.models import model as M
    from repro.models.layers import QuantContext
    from repro.models.quantize import quantize_tree

    cfg = program_config(c)
    t, k = time.monotonic(), clock.total
    params = weights.make(layout, seed)
    jax.block_until_ready(params)
    marks["init_s"], marks["init_compile_s"] = time.monotonic() - t, clock.total - k
    t, k = time.monotonic(), clock.total
    obs = calibration.Observer()
    toks = jnp.asarray(weights.calibration_tokens(c, seed), jnp.int32)
    M.apply(params, {"tokens": toks}, cfg,
            ctx=QuantContext(ql.W8A8_INT8, observer=obs), mode="train", unroll=True)
    marks["calibrate_s"], marks["calibrate_compile_s"] = (time.monotonic() - t,
                                                          clock.total - k)
    t, k = time.monotonic(), clock.total
    qparams = quantize_tree(params, ql.W8A8_INT8,
                            tables=calibration.stack_tables(obs.tables()))
    jax.block_until_ready(qparams)
    del params
    gc.collect()
    marks["quantize_s"], marks["quantize_compile_s"] = (time.monotonic() - t,
                                                        clock.total - k)
    return cfg, qparams
