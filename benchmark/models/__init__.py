"""One adapter a model: a configuration file states ``"model": "<name>"``
and ``common.load_model`` loads ``models/<name>.py`` by path. The cell
runners know traffic; the adapter is the only place that reads the
configuration's model keys. Every function takes ``cfg`` whole and no
size by position.

A ``train_job`` cell needs:

- ``build_net(cfg, seed, optimizer=None)``: the program's net on its
  normal path, holding the benchmark's seeded weights (and, with
  ``optimizer``, its updater state);
- ``describe(cfg)``: one line for the log;
- ``encode_batch(tokens, cfg)``: ``[B, T + 1]`` token ids to the
  ``(features, labels)`` that ``fit_scan`` takes for one step;
- ``start_params(seed, cfg)``: the seeded start in the net's own layout,
  yielded a layer (or a few) at a time as ``{layer: {leaf: array}}``;
- ``train_reference(seed, cfg, hyper, batches, prec)``: the plain
  reference following the job's first steps (``losses``,
  ``grad_norms``, ``delta_norms``);
- ``flops``: the counts of operations and bytes that readers get as
  ``obs["flops"]``.

A served cell (``open_loop``, ``closed_loop``) needs ``build_net``,
``describe``, ``flops`` and ``served_gaps(seed, cfg, samples,
control=None)``; requests carry token ids over the wire, so nothing
encodes them.

The reference is float32 at precision ``highest``, written with
``benchmark.reference.mm`` so that ``prec`` (and with it the control
named in the configuration's ``check.control``) reaches every product;
``reference.follow_steps`` and ``reference.served_gaps`` are the loops.
What else a model needs (its weights from the seed, its counts) it
keeps in files of its own beside its adapter.
"""
