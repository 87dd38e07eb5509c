"""Stream kinds: what one step of a configuration's input stream is, found
by name (``catalog.stream_kind``). A configuration names its kind in
``dataset["kind"]``; without that key the kind is ``snapshots``.

A kind is a module here with the functions in ``FUNCTIONS``:

``build(cfg, fields, seed)``  the seeded stream for the configuration
                              and its plan's fields (padded sizes
                              included); it has ``len()``, ``n_global``
                              (rows of the recurrent state) and ``feat``
                              (the global node-feature table the session
                              serves from)
``item(stream, k)``           what ``SnapshotServer.run_multi`` takes for
                              step ``k``
``rows(stream, k)``           step ``k``'s active global rows
``live_rows(stream, k)``      the number of live output rows of step ``k``
``work(stream, k, family_work, m)``
                              step ``k``'s ``(flops, bytes)``, through the
                              family's ``work`` module and the model's
                              widths ``m``
``prepare(stream, k)``        step ``k``'s padded arrays for the plain
                              reference: ``x``, ``n`` (live output rows)
                              and ``live`` (1) among them

and ``WORK``, the functions of a family's ``work`` module that ``work``
calls.
"""

FUNCTIONS = ("build", "item", "rows", "live_rows", "work", "prepare")
