"""Engine factory for real compute: one replica per device.

`real_engine_factory` returns the `engine_factory(cfg, tp, gpu)` callable
that `ControlPlane` calls when a Slurm job starts. The job's cluster-wide
GPU slot picks the device; that replica's parameters and KV pool live on
it. Parameters are random, drawn from `seed` (the same on every device, so
replicas answer identically), and made once per device.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax

from repro.config import TPU_V5E, HardwareConfig, ModelConfig, hardware_for
from repro.engine.engine import LLMEngine
from repro.engine.executor import RealExecutor
from repro.models import api

# static sizes of one replica's engine (vLLM's serve arguments)
NUM_BLOCKS = 512
BLOCK_SIZE = 16
MAX_NUM_SEQS = 8
MAX_MODEL_LEN = 1024
MAX_PREFILL_TOKENS = 1024


def serving_setup(cfg: ModelConfig, device, *, cpu_rehearsal: bool):
    """(config, roofline constants, paged-attention backend) to serve on
    `device`: the config as published with the compiled kernel, or, for a
    CPU rehearsal, the reduced config with interpreted kernels, costed as
    the v5e it stands in for."""
    if cpu_rehearsal:
        return cfg.reduced(), TPU_V5E, "interpret"
    return cfg, hardware_for(device), None


def real_engine_factory(cfg: ModelConfig, devices: Sequence, *,
                        hw: HardwareConfig, backend: Optional[str] = None,
                        seed: int = 0):
    """`devices[gpu]` serves the job whose GPU slot is `gpu`, with the
    sizes above. `backend` None runs the compiled kernel (TPU only); `hw`
    gives the roofline constants of the chip."""
    params_on: dict = {}

    def factory(c: ModelConfig, tp: int, gpu: int) -> LLMEngine:
        if c != cfg:
            raise ValueError(f"factory serves {cfg.name}, asked for {c.name}")
        if not 0 <= gpu < len(devices):
            raise ValueError(f"GPU slot {gpu} has no device "
                             f"({len(devices)} devices)")
        dev = devices[gpu]
        if dev not in params_on:
            with jax.default_device(dev):
                params, _ = api.init_params(cfg, jax.random.key(seed))
            params_on[dev] = jax.device_put(params, dev)   # commit to dev
        ex = RealExecutor(cfg, params_on[dev], num_blocks=NUM_BLOCKS,
                          block_size=BLOCK_SIZE, hw=hw, tp=tp,
                          backend=backend, max_model_len=MAX_MODEL_LEN,
                          max_slots=MAX_NUM_SEQS, device=dev)
        return LLMEngine(cfg, ex, num_blocks=NUM_BLOCKS,
                         block_size=BLOCK_SIZE, max_num_seqs=MAX_NUM_SEQS,
                         max_prefill_tokens=MAX_PREFILL_TOKENS,
                         max_model_len=MAX_MODEL_LEN)

    return factory
