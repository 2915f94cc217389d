"""One module per architecture: everything the benchmark knows about a
model's shape. A configuration file names its module with
``"arch": "<module>"``, and the harness loads ``bench/arch/<module>.py``
by that name (``harness.load_arch``), once per cell. Nothing outside
these modules knows a model's layers, weights or operations, so a new
architecture comes in as new files: its module, its configuration file,
its cells and its metrics.

A module gives:

``Dims``, ``dims(cfg) -> Dims``
    The sizes the configuration file states, as a frozen dataclass. It
    has ``vocab``, the published vocabulary: the traffic draws prompt
    ids below it. An architecture whose layers are split over chips
    states here which share this chip holds (say, experts ``[a, b)`` of
    each layer).

``TINY``
    Configuration keys that cut the model to a size the CPU dry run
    holds (``--tiny-cpu``); the serving sizes are the harness's.

``program_config(name, m) -> repro.config.ModelConfig``
    The program's configuration for these sizes, with weights in the
    compute dtype as ``launch/serve`` serves them. The only function of
    a module that may import ``repro``.

``program_params(m, key, dtype) -> tree``
    Every weight, in the program's own tree, shapes and dtype, drawn
    from ``key`` with ``weights.normal``. It is traced under
    ``jax.jit`` and runs on the device in one call. The harness checks
    the tree against the program's own ``init`` before a run. Where the
    chip holds a share of a layer (its experts), it makes that share.

``ref_embed(m, key, tokens, control)``, ``ref_blocks(m)``, ``ref_logits(m, key, h, control)``
    The plain reference, in float32, importing nothing of ``repro`` and
    taking nothing the program made: it regenerates the weights from
    ``key`` as ``program_params`` draws them. ``ref_embed`` maps padded
    tokens ``[rows, positions]`` to hidden states; ``ref_blocks`` lists
    ``(fn, layer indices)`` in order, ``fn(m, x, key, i, control)``
    taking layer ``i`` (a traced int32) over ``x``, so a leading dense
    layer and a stack of expert layers are two blocks, each compiled
    once; ``ref_logits`` maps hidden states ``[T, d]`` to logits over
    the published vocabulary. ``control=True`` computes the same piece
    one precision below the configuration's, the check's control.
    ``reference.py`` holds the shared numerics (``mm``, ``rmsnorm``,
    ``rotary``, ``fp8_round``) and runs the pieces.

``step_flops(m, step)``, ``step_bytes(m, step)``
    The operations and bytes one engine step needs, whatever implements
    it. ``step`` is a ``spans.Step``: each seated slot's context (the
    positions its token attends over) and ``counters``, the per-step
    readings an architecture's spans add (an expert layer needs the
    experts each layer hit, not only the contexts).
"""
