"""The replica the serving cells deploy: the program's EngineReplica, plus
what only the process that holds the chip can do for the benchmark — make
the seeded weights in one jitted call, hold the engine to the plain
reference, trace the device, and report its memory.

Requests still take the program's whole path: Serve router -> replica ->
`stream_generate` -> LLMEngine.  Nothing here touches that path.
"""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, List

from ray_tpu.llm.serving import EngineReplica

from . import families, refcheck, trace

class BenchReplica(EngineReplica):

    def __init__(self, config: Dict[str, Any], *, seed: int, **engine):
        import jax

        import ray_tpu.llm.engine as engine_mod
        family = families.load(config["family"])
        self._bench_config = config
        self._bench_family = family
        cfg = family.program_config(config, max_seq_len=engine["max_len"])
        # Weights on the device from the seed in ONE jitted call, in the
        # type they are served in.  The engine's constructor calls
        # `init_params` eagerly, leaf by leaf (a dozen RNG programs, 68 s
        # cold); it takes no initializer, so the name it looks up is
        # swapped for the constructor's duration.
        eager = engine_mod.init_params
        engine_mod.init_params = lambda c, key: jax.block_until_ready(
            jax.jit(eager, static_argnums=0)(c, key))
        try:
            super().__init__(cfg, seed=seed, **engine)
        finally:
            engine_mod.init_params = eager

    # --------------------------------------------------------- checking --
    async def bench_check(self, prompt: List[int], served: List[List[int]]
                          ) -> Dict[str, Any]:
        """Hold what the engine served for `prompt` (cold, then as a
        prefix-cache hit) to the float32 reference: `refcheck.report`."""
        loop = asyncio.get_running_loop()
        async with self._lock:              # no tick while this computes
            return await loop.run_in_executor(
                None, self._bench_check, prompt, served)

    def _bench_check(self, prompt, served) -> Dict[str, Any]:
        # `LLMEngine._run_prefill` (private) gives the plain comparison its
        # logits; a family that owns its check gets the engine itself.
        return refcheck.report(self.engine, self._bench_family,
                               self._bench_config, prompt, served)

    async def bench_warm_sampler(self) -> int:
        """The engine samples the first tokens of every admission wave in
        one call whose programs depend on the wave's size; each size
        compiles in milliseconds, too short for the persistent cache to
        keep.  Run every size once here, so that none compiles inside the
        window the first time that many requests are admitted together."""
        import jax.numpy as jnp

        from ray_tpu.llm.engine import SamplingParams
        sample = getattr(self.engine, "_sample_batch", None)
        if sample is None:
            return 0
        logits = jnp.zeros((self.engine.cfg.vocab_size,), jnp.float32)
        async with self._lock:
            for n in range(1, self.engine.max_batch + 1):
                sample([logits] * n, [SamplingParams()] * n)
        return self.engine.max_batch

    # ---------------------------------------------------------- tracing --
    async def bench_trace(self, seconds: float, out_dir: str,
                          keep_ops: int) -> Dict[str, Any]:
        """Trace this process's device for `seconds` while requests keep
        flowing, reduce the trace here, keep a cut of it, drop the raw
        file.  Returns the reduction and the traced interval (wall)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, trace.traced, lambda: time.sleep(seconds), out_dir,
            keep_ops)

    async def bench_memory(self) -> Dict[str, Any]:
        import jax
        return {"stats": [d.memory_stats() or {}
                          for d in jax.local_devices()]}
