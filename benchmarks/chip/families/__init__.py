"""Architecture families: what the benchmark knows of one kind of model.

A configuration file names its family (``"family": "gqa"``); the harness
loads ``families/<family>.py`` by path (``spec.family``).  A configuration
of another architecture is therefore new files only: a family module, a
configuration file, and ``BENCHMARK.json`` entries.  Code that serves every
family stays outside: seeds and the weights' draw (``weights.py``), the
float32 helpers, padding and gap of the reference (``reference.py``), and
the roofline's least time (``flops.py``).

Every family module provides the names in ``INTERFACE``:

``program_config(file: dict, rehearse: bool)``
    The program's configuration (``repro.configs``) for the configuration
    file: the registry's entry for ``file["arch"]``, checked key by key
    against every published key the family maps, with ``norm_eps`` from
    ``rms_norm_eps``.  Each key the file lists under ``reduced`` (the same
    list as its ``BENCHMARK.json`` entry; ``spec.load_cell`` holds the two
    equal) is applied instead of checked, as the experts held on this chip
    would be.  A key the family cannot apply exits, naming it.  With
    ``rehearse`` the program's tiny ``reduced()`` variant in the served
    dtype.
``Shape`` with ``Shape.of(cfg)``
    A frozen, hashable record of what the weights, the reference and the
    counts need of the model: static under ``jax.jit``.  It has at least
    ``vocab`` (the real vocabulary, which the traffic draws token ids from
    and the logits are taken over) and ``layers``.
``make(shape, seed)``
    Seeded random weights in a plain layout of the family's choosing, in
    the served dtype, made on the device in one jitted call
    (``weights.key_of``, ``weights.draw``).
``to_program(w, shape)``
    The program's parameter tree over the same arrays.  The tree is the
    family's business: prologue layers, several period slots and expert
    banks are all the program's ``model.init_params`` layout.
``param_count(shape)``
    Matrix parameters: ``{"stack": ..., "head": ...}``.
``logits_at(shape, w, tokens, pos, length, n_pos, fp8=False)``
    The plain float32 reference over the plain layout at
    ``Precision.HIGHEST``, nothing imported from the program: logits
    ``(len(pos), vocab)`` of one sequence padded to ``length``
    (``reference.padded``); ``fp8`` rounds every matrix product's operands
    to float8 (``reference.fp8``), the control the comparison must fail.
    Where the model holds a chip's share of a layer (its experts), the
    reference is given the same share.
``decode_step(shape, lengths)``
    Operations of one decode forward of the slots whose valid context
    lengths are ``lengths`` (``metrics/decode_mfu.py``).

A family whose attention runs the ``paged_decode`` kernel also provides
``paged_decode_step(shape, lengths)``: (operations, bytes) of all the
kernel's calls in one decode step, however many of its layers make one
(``metrics/paged_decode_roofline.py``).
"""

INTERFACE = ("program_config", "Shape", "make", "to_program", "param_count",
             "logits_at", "decode_step")
