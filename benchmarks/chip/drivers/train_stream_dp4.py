"""``train_stream.py`` on a data-parallel mesh: the same corpus, batch,
optimizer and window, with ``TrainEngine(mesh_data=<n>)`` over ``n``
chips, so each step splits its batch (and each width-sorted chunk)
along the mesh's ``data`` axis, shards the parameters by the model's
axis rules, and all-reduces the gradients.

Traffic parameters: those of ``train_stream.py``, and ``mesh_data``.
``correct`` is ``train_stream.py``'s: the first three steps against the
single-device reference (``reference.train_steps``).

Every seed does the same work: each epoch's batches are composed from
the corpus seed and only their order comes from the run's seed
(``even_batches``). Composed from the run's seed, a batch's width-sorted
chunks pad a different share of positions on every seed: costed in
positions, the window's tokens a second then spread by 0.43-0.49% over
seeds, half the cell's 1% bound; composed from the corpus seed, 0.08%.

``FAULTS`` holds the fault a mesh adds to ``faults.py``'s, which
``calibrate_mesh.py`` plants: ``quarter_batch``, each step taken on the
first quarter of its rows alone, as one of four chips steps when the
gradients are not exchanged."""
from __future__ import annotations

import numpy as np

from chip import harness as H
from chip.drivers import train_stream as TS


def even_batches(loader, batch_seed: int) -> None:
    """Give ``loader`` (``batch_max`` buckets) an epoch plan whose
    batches are the same on every seed, chopped from a permutation of
    ``(batch_seed, epoch)``; the loader's own seed orders them."""
    assert loader.bucket_by is not None \
        and loader.bucket_mode == "batch_max", "batch_max loaders only"

    def plan(epoch):
        cached = loader._plan
        if cached is not None and cached[0] == epoch:
            return cached[1]
        rows = np.random.default_rng((batch_seed, epoch)).permutation(
            len(loader.source))
        chopped = []
        loader._chop(rows, None, chopped)
        order = np.random.default_rng((loader.seed, epoch)).permutation(
            len(chopped))
        batches = [(idx, int(loader.bucket_by[idx].max()))
                   for idx, _ in (chopped[i] for i in order)]
        loader._plan = (epoch, batches)
        return batches

    loader._epoch_plan = plan


def quarter_batch(step):
    """A step over the batch's first quarter of rows: the gradient one
    chip of four holds before the all-reduce, applied as the batch's."""
    def local(state, ids, yy):
        n = ids.shape[0] // 4
        return step(state, ids[:n], yy[:n])
    return local


FAULTS = {"quarter_batch": ("step", quarter_batch)}


def run(cell, **kw):
    import jax
    from repro.core import trainer as TR
    from repro.data import pipeline as PIPE

    n = int(cell.traffic["mesh_data"])
    if jax.device_count() < n:
        raise H.BenchError(f"the cell's mesh needs {n} devices, JAX "
                           f"found {jax.device_count()}")
    real = TR.TrainEngine

    class OnMesh(real):
        def __init__(self, *a, **overrides):
            overrides.setdefault("mesh_data", n)
            super().__init__(*a, **overrides)

        def make_loader(self, train, y):
            loader = super().make_loader(train, y)
            even_batches(loader, int(cell.traffic["corpus_seed"]))
            return loader

        def compile_chunks(self, loader, state, opt_cfg, y):
            """The engine's warm-up of every chunk program, again on the
            parameters an update hands back, until their shardings
            repeat: on a mesh ``apply_grads`` returns them in other
            shardings than ``fit`` placed, and each chunk program is
            then another executable, which would otherwise load inside
            the window at the first batch with that chunk's width."""
            super().compile_chunks(loader, state, opt_cfg, y)
            shapes, seen = loader.chunk_shapes(), set()
            while shapes:
                key = tuple(x.sharding for x in jax.tree.leaves(state))
                if key in seen:
                    return
                seen.add(key)
                rows, width = shapes[-1]
                zeros = self.place_batch({
                    "ids": np.zeros((rows, width), np.int32),
                    "y": np.zeros((loader.local_batch,) + y.shape[1:],
                                  y.dtype)})
                chunk = TR.chunk_grad(self.apply_fn, self.heads, state[0],
                                      zeros["ids"], zeros["y"], 0)
                state, _ = TR.apply_grads(opt_cfg, state,
                                          (chunk,) * PIPE.CHUNKS)
                super().compile_chunks(loader, state, opt_cfg, y)

    # train_stream.py builds TrainEngine with no mesh and takes no
    # mesh from its traffic, so the class is swapped for the run alone
    TR.TrainEngine = OnMesh
    try:
        return TS.run(cell, **kw)
    finally:
        TR.TrainEngine = real
