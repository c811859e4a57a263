"""Plain references, one module per model family, named by a configuration
file's ``reference`` key and loaded by path (``run.load_module``).

A reference imports nothing of the program under test and computes
everything from the weights the benchmark made (``bench/weights.py``). What
the harness asks of one:

``Dims.from_hf(model) -> Dims``
    The sizes the reference needs, from the file's ``model`` (the
    published ``config.json``, as run). A frozen dataclass, so that jitted
    functions take it as a static argument. It has ``vocab``.
``Precision(**spec)``
    Bit widths of the integer paths, from the file's ``precision``
    ``reference`` and ``control`` entries.
``token_gaps(params, dims, tokens, at, served, prec, keep) -> (gaps, first)``
    At each compared position, how far the served token's logit lies
    below the best, and the token this precision puts first
    (``correctness.gaps``).
``fisher_diag(params, dims, tokens)`` and ``prune_units(params, dims, sq,
calib, step_frac, max_steps, delta_ax) -> (keep, n_drop)``
    HQP's sensitivities and conditional prune; configurations with an
    ``hqp`` entry only.
``program_sizes(model) -> {attribute: value}``
    What the program's ``ModelConfig`` must hold for the file's sizes;
    ``run.program_config`` compares each, a dotted name (``ssm.d_state``)
    reading through nested configurations, and refuses an empty map.
``work_model(dims, served, int8) -> work.Model``
    The layers of the served model, each with its cache attention, its
    linears and any extra calls of a kernel class, from the served
    parameters' widths; ``int8`` where the configuration serves the HQP
    artifact (W8A8 linears, INT8 KV).

A configuration file may also list ``kernels``: ``[class, name prefix]``
pairs that ``devtrace`` classes the cell's trace by, after its own.
"""
