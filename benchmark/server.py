"""The served class of the serving cells: one model replica.

Deployed by ``kt.cls(BenchServer, ...).to(kt.Compute(tpus="v5e-1"))`` exactly
as ``chip_smoke.py`` deploys ``examples/llama_serve.py::LlamaServer``, so the
normal path is what is timed: pod server -> worker (the one process that
holds the chip) -> ``DecodeEngine(RollingGenerator)``. It differs from
``LlamaServer`` in what a benchmark needs: the model comes from a
configuration FILE (not a preset name) through the file's family
(``benchmark/families``: the program's configuration object and the weights
from the seed, which the float32 reference can rebuild), there is no static
``Generator`` beside the engine, and the worker can trace itself. The family
chooses neither this class nor the engine path.
"""

from __future__ import annotations

import json
import os
import time


class BenchServer:
    def __init__(self, config_file: str, deployment: dict, seed: int = 0,
                 warm=()):
        import jax

        from benchmark import families
        from kubetorch_tpu.models.rolling import RollingGenerator
        from kubetorch_tpu.observability import devstats
        from kubetorch_tpu.serving.engine import DecodeEngine

        self._t_init = time.perf_counter()
        self._compiles = devstats.watch_compiles()
        config = json.load(open(config_file))
        dep = dict(deployment)
        family = families.load(config, "serve")
        cfg = family.program_config(config, "serve", dep)
        params = jax.block_until_ready(
            family.serving_tree(seed, family.dims(config)))
        self._weights_s = time.perf_counter() - self._t_init
        self.cfg, self.deployment = cfg, dep
        self._generator = RollingGenerator(
            params, cfg, max_slots=dep["max_slots"], max_len=dep["max_len"],
            steps_per_call=dep["steps_per_call"],
            prefill_chunk=dep["prefill_chunk"],
            admit_width=dep.get("admit_width", 0),
            kv_dtype=config["kv_dtype"], seed=seed & 0x7FFFFFFF)
        # Warm every executable the cell's lengths can reach, before the
        # engine's driver thread exists: ``warm`` is [[rows, prompt_len],
        # ...]; rows that arrive together share one admission (width 1 or
        # admit_width), a prompt longer than prefill_chunk takes the chunked
        # path, and each run() ends in the decode chunk.
        # (``RollingGenerator.warmup`` caps its prompts at max_len // 2, so
        # it never reaches the top bucket.)
        t0 = time.perf_counter()
        for rows, length in warm:
            for _ in range(rows):
                self._generator.submit([1] * length, max_new_tokens=1)
            self._generator.run()
        self._warm_s = time.perf_counter() - t0
        self._engine = DecodeEngine(self._generator,
                                    admit_rows=dep.get("admit_rows"))
        self._trace_dir = None

    # ----------------------------------------------------------- serving
    def generate(self, program):
        yield from self._engine.generate(program)

    def setup_report(self):
        return {"warm_s": self._warm_s, "weights_s": self._weights_s,
                "compile": dict(self._compiles)}

    def stats(self):
        return self._engine.stats()

    def snapshot(self):
        """The engine's counters and the compile counters at one instant."""
        return {"stats": self._engine.stats(),
                "compile": dict(self._compiles)}

    # ----------------------------------------------------------- tracing
    def trace_start(self, directory: str):
        import jax

        os.makedirs(directory, exist_ok=True)
        self._trace_dir = directory
        jax.profiler.start_trace(directory)
        return self.snapshot()

    def trace_stop(self):
        import jax

        snap = self.snapshot()
        jax.profiler.stop_trace()
        return snap

    def trace_reduce(self, marks):
        from benchmark import trace

        return trace.reduce_dir(self._trace_dir, marks)

    # ------------------------------------------------------------ report
    def device_report(self):
        import jax

        devices = jax.devices()
        return {
            "pid": os.getpid(),
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory": [{k: (dev.memory_stats() or {}).get(k) for k in (
                "bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
                for dev in devices],
            "compile": dict(self._compiles),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "n_params": int(sum(x.size for x in jax.tree.leaves(
                self._generator.params))),
        }
