"""Pipeline selection and compile layer (``fused_program.get_pipeline``):
pipelines built inside the measured window, from the program's
``engine.pipeline_cache.miss`` counter. Set-up warms every shape, so a
sound window reads 0."""


def read(w):
    c = w.counters
    if c is None or not c.get("engine.flushes"):
        return None
    return c.get("engine.pipeline_cache.miss", 0)
