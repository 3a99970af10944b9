"""Named host spans at the program's layer boundaries.

A span is a ``jax.profiler.TraceAnnotation`` named ``s2ce.<name>``. With
no profiler recording it costs about a microsecond; while one records
(``jax.profiler.start_trace``) it lands in the trace on the same clock as
the device's programs, with its keyword arguments as stats. There is no
switch: tracing is on exactly while a profiler runs. Spans of one batch
or wave carry ``step``, or sit inside a span that does.

Inside the jitted model programs, ``jax.named_scope`` names the device
work of the latent attention and expert layers in each op's HLO metadata
(``op_name``), which a trace viewer shows beside the op; they cost
nothing at run time:

- ``s2ce.mla.decode_latent``: a decode step's latent attention, scored
  against the latent cache (``models/attention.py``);
- ``s2ce.mla.prefill``: latent attention expanded into per-head keys and
  values (prefill and full-sequence passes);
- ``s2ce.moe.route``: the router, its top-k and the balance loss
  (``models/moe.py``);
- ``s2ce.moe.experts``: the held experts (dense for a few tokens, else
  grouped products) and the shared experts;
- ``s2ce.scan.param_barrier``: the layer scan's barrier on each layer's
  parameter slice (``models/transformer.py``). It is there only where a
  mesh of more than one device can all-gather a layer's weights; a
  program without it reads each layer's weights in place from the stack.
  The lowered program's locations name it; the compiler may drop the
  barrier itself from the compiled text.
"""

import jax

PREFIX = "s2ce."

# every span the program opens; ``op.`` is a family, one per op name
NAMES = (
    "execute_batch",        # Orchestrator.execute_batch (step, events)
    "stage_batch",          # batch to the device, per-step rng
    "op.",                  # each op of the graph, around its call
    "uplink",               # the wire codec's round trip between sides
    "drift_check",          # the host read of the drift flag
    "drift_response",       # each op's drift reset
    "sla_observe",          # the SLA tracker's update
    "control.topology",     # Orchestrator.topology_step (step)
    "control.observe",      # OffloadController.observe (step)
    "control.apply",        # Orchestrator.apply_decision (step)
    "control.elastic",      # Orchestrator.elastic_step (step)
    "control.replan",       # OffloadController.replan (step, reason)
    "control.rescale",      # Orchestrator._apply_rescale (step)
    "serve.prefill",        # prefill and the first sample (rows, len)
    "serve.decode_step",    # one call of the decode program (i)
    "serve.gather",         # the generated tokens collected
)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
