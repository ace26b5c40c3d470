"""Architectures, one module each: ``archs/<name>.py``, chosen by the
configuration file's ``"arch"`` key (``dense`` where the key is absent)
and loaded by path (:func:`chipbench.registry.arch`), so a new
architecture is a new file.  It imports what it shares from
``chipbench`` by absolute import, and nothing of the program.

A module provides:

``sizes(config) -> sizes``
    the configuration file read into a hashable value (the jitted
    reference takes it as a static argument);
``maker(sizes, out_shardings=None) -> (key -> params)``
    the weights from a key of the seed (:func:`chipbench.reference.seed_key`)
    as one jitted program, placed in ``out_shardings``;
``run(sizes, hp, seed, batches, mode="f32", fault=None) -> Readings``
    the plain float32 reference of the first ``len(batches)`` train
    steps from the seed's weights, on one chip; ``mode="fp8"`` is the
    control, ``fault`` one of ``"half_batch"``, ``"loss_altered"`` and
    ``"no_exchange"`` (attention cut at the workers' frames);
``model_flops(sizes, seqlens) -> int``
    the operations a train step on those document lengths requires,
    counting active weights only;
``attention_least_seconds(sizes, seqlens, peak) -> (seconds, roof)``
    the least time the chip could spend on that step's attention.

What does not depend on the architecture (``Hyper``, ``Readings``, the
leaf norms, the comparison) stays in :mod:`chipbench.reference` and
:mod:`chipbench.compare`.
"""
