"""Generate docs/API.md: every public symbol (module ``__all__``) with the
first line of its docstring. Run from the repo root:

    JAX_PLATFORMS=cpu python tools/gen_api.py
"""

import importlib
import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Force CPU so a doc build never claims an accelerator.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

MODULES = [
    "horovod_tpu",
    "horovod_tpu.collective",
    "horovod_tpu.optimizer",
    "horovod_tpu.optimizer_sharded",
    "horovod_tpu.compression",
    "horovod_tpu.fusion",
    "horovod_tpu.adasum",
    "horovod_tpu.process_set",
    "horovod_tpu.spmd",
    "horovod_tpu.config",
    "horovod_tpu.callbacks",
    "horovod_tpu.timeline",
    "horovod_tpu.tracing",
    "horovod_tpu.autotune",
    "horovod_tpu.checkpoint",
    "horovod_tpu.checkpoint_sharded",
    "horovod_tpu.faults",
    "horovod_tpu.data",
    "horovod_tpu.elastic",
    "horovod_tpu.elastic.driver",
    "horovod_tpu.runner.launcher",
    "horovod_tpu.overlap",
    "horovod_tpu.parallel",
    "horovod_tpu.parallel.mesh",
    "horovod_tpu.parallel.mp",
    "horovod_tpu.parallel.pipeline",
    "horovod_tpu.parallel.fsdp",
    "horovod_tpu.parallel.conjugate",
    "horovod_tpu.models",
    "horovod_tpu.models.gpt2_pipeline",
    "horovod_tpu.models.llama",
    "horovod_tpu.models.sdar",
    "horovod_tpu.models.lfm2",
    "horovod_tpu.models.glm4_moe_lite",
    "horovod_tpu.models.smallthinker",
    "horovod_tpu.models.t5",
    "horovod_tpu.models.convert",
    "horovod_tpu.models.generate",
    "horovod_tpu.profiler",
    "horovod_tpu.timeseries",
    "horovod_tpu.health",
    "horovod_tpu.blackbox",
    "horovod_tpu.confbus",
    "horovod_tpu.serving",
    "horovod_tpu.serving.cache",
    "horovod_tpu.serving.scheduler",
    "horovod_tpu.serving.engine",
    "horovod_tpu.serving.disagg",
    "horovod_tpu.serving.replica",
    "horovod_tpu.serving.transport",
    "horovod_tpu.serving.fleet",
    "horovod_tpu.serving.reqtrace",
    "horovod_tpu.ops.attention",
    "horovod_tpu.ops.flash_attention",
    "horovod_tpu.ops.ring_attention",
    "horovod_tpu.ops.ring_flash",
    "horovod_tpu.ops.sequence",
    "horovod_tpu.ops.moe",
    "horovod_tpu.ops.short_conv",
    "horovod_tpu.ops.sync_batch_norm",
    "horovod_tpu.ops.quantized",
    "horovod_tpu.ops.tile_table",
    "horovod_tpu.data.store",
    "horovod_tpu.data.packing",
    "horovod_tpu.data.prefetch",
    "horovod_tpu.spark.common.store",
    "horovod_tpu.spark.common.util",
    "horovod_tpu.torch",
    "horovod_tpu.torch.elastic",
    "horovod_tpu.tensorflow",
    "horovod_tpu.tensorflow.keras",
    "horovod_tpu.tensorflow.elastic",
    "horovod_tpu.keras",
    "horovod_tpu.lightning",
    "horovod_tpu.spark",
    "horovod_tpu.spark.lightning",
    "horovod_tpu.ray",
    "horovod_tpu.cluster",
    "horovod_tpu.utils.stall",
    "horovod_tpu.utils.random",
    "horovod_tpu.native",
]


def first_line(obj) -> str:
    if isinstance(obj, (int, float, str, bytes, tuple, list, dict)):
        return ""              # constants: the builtin docstring is noise
    doc = inspect.getdoc(obj) or ""
    line = doc.strip().split("\n", 1)[0].strip()
    if " object at 0x" in line:
        return ""  # synthesized dataclass docstring embeds addresses —
        # non-deterministic output would churn the committed file
    if line.startswith("partial(func,"):
        return ""  # functools boilerplate, not a summary
    return line


def main() -> None:
    out = ["# API reference (generated — `python tools/gen_api.py`)",
           "",
           "Every public symbol, grouped by module; one-line summaries "
           "from docstrings. See docs/MIGRATING.md for the upstream-API "
           "mapping.", ""]
    for name in MODULES:
        try:
            mod = importlib.import_module(name)
        except Exception as e:
            out.append(f"## `{name}` — import failed: {e}")
            out.append("")
            continue
        symbols = getattr(mod, "__all__", None)
        if not symbols:
            symbols = [k for k, v in vars(mod).items()
                       if not k.startswith("_") and
                       not inspect.ismodule(v) and
                       getattr(v, "__module__", name) == name]
        out.append(f"## `{name}`")
        mline = first_line(mod)
        if mline:
            out.append(f"*{mline}*")
        out.append("")
        for s in symbols:
            line = first_line(getattr(mod, s, None))
            out.append(f"- `{s}`" + (f" — {line}" if line else ""))
        out.append("")
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "API.md")
    with open(path, "w") as f:
        f.write("\n".join(out))
    print(f"wrote {path}: {len(MODULES)} modules")


if __name__ == "__main__":
    main()
