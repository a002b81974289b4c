"""Set-up as the program books it in its own metrics registry
(``horovod_tpu.profiler``), read in the benchmark's process: the builder's
step through trace, lowering and compile (``compile_seconds{stage, fn}``)
and the set-up spans (``span_seconds{span}``). The registry outlives
``hvd.shutdown()``, so the readers find it after the window; a program
that books none of it gives nothing. Imports the program inside the
functions, so ``run.py --check`` stays off JAX."""


def step_seconds(stage):
    """Seconds of ``stage`` (``trace``, ``lower``, ``compile``) in the
    latest build of the builder's step: every ``fn`` of
    ``compile_seconds`` but ``other`` (a cell's process builds one step),
    else None."""
    from horovod_tpu.observability import metrics

    family = metrics.snapshot().get("compile_seconds")
    if family is None:
        return None
    got = [v for k, v in family["samples"].items()
           if f"stage={stage}" in k.split(",")
           and "fn=other" not in k.split(",")]
    return sum(got) if got else None


def span_seconds(name):
    """Host seconds of the set-up span ``name``'s latest run, else None."""
    from horovod_tpu.observability import metrics

    return metrics.value("span_seconds", span=name)
