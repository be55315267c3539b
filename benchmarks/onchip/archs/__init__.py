"""One module per architecture, named by a configuration file's
``"architecture"`` key and found by ``cells.arch``. A new architecture is a
new file here; no other module of the benchmark names a weight leaf or a
published config key.

Each module takes the configuration file as a dict (``conf``: its
``config`` holds the published keys, its ``norm`` the norm) and gives:

    shapes(conf)            {path: (shape, fan_in or None, kind)} of every
                            weight leaf, in the order ``weights.py`` draws
                            them (see there for paths and kinds)
    program_fields(conf)    the program's ``ModelConfig`` fields, with
                            ``norm``, ``act`` and ``norm_eps`` among them
                            (``system.model_config`` checks and applies them)
    program_tree(w)         the benchmark's weights in the program's
                            parameter tree (``system.program_params``
                            checks it leaf by leaf)
    logits_at(conf, w, tokens, pos, mode)
                            the float32 reference (``mode="fp8"``: the
                            control), as ``reference.served_gap`` calls it
    Counts(conf)            operations and bytes of the served work
                            (``flops.Counts``)
    rehearsal(conf)         a copy of ``conf`` at the CPU rehearsal's sizes

and may give, for ``tools/aot.py`` alone:

    reference_layer(conf, w, rows, seq_len, mode)
                            the program ``logits_at`` runs once per layer,
                            lowered for abstract weights; without it the
                            tool compiles the serving programs only

A module imports nothing of the program: ``system.py`` is the only module
that does.
"""
