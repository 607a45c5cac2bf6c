"""The four-device cell at its rehearsal size, in a process of its own
(the tests run it with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
on the CPU): one JSON line per check.

    python tests/chipbench/mesh_rehearsal.py
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (HERE, ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CELL = "qwen2-7b.round.fedit.4chip"


def emit(**kv):
    print(json.dumps(kv), flush=True)


def _bits(x):
    import numpy as np

    x = np.asarray(x)
    return x.view({2: np.int16, 4: np.int32}[x.dtype.itemsize]).astype(
        np.int64)


def weights_check(seed: int):
    """The base and the starting adapter made in the mesh's shardings
    against the same makers on one device (bit for bit) and in the
    shardings the runner places them with; the base also against the
    one-call maker of one-chip cells (to the last bit)."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness, round_cell, weights
    from repro.launch.sharding import params_shardings

    cell = harness.Cell(ROOT, CELL, seed, 1.0, False, True, 0.0)
    m, rank = cell.model, cell.params["lora_rank"]
    devices = harness.cell_devices(cell, True)
    mesh = round_cell.cell_mesh(cell, devices)
    one = SingleDeviceSharding(devices[0])
    out = []
    for shapes, make, plain in (
            (weights.base_shapes(m),
             lambda sh: weights.base_params(m, seed, sh),
             weights.base_params(m, seed)),
            (jax.eval_shape(lambda: weights.init_lora(m, 0, rank)),
             lambda sh: weights.init_lora(m, seed, rank, sh),
             weights.init_lora(m, seed, rank))):
        sh = params_shardings(mesh, shapes)
        sharded = make(sh)
        alone = make(jax.tree.map(lambda _: one, shapes))
        for (path, a), b, c, s in zip(
                jax.tree_util.tree_flatten_with_path(alone)[0],
                jax.tree.leaves(sharded), jax.tree.leaves(plain),
                jax.tree.leaves(sh)):
            off = np.abs(_bits(c) - _bits(b))
            out.append({
                "leaf": jax.tree_util.keystr(path),
                "equal": a.dtype == b.dtype and a.shape == b.shape
                and np.asarray(a).tobytes() == np.asarray(b).tobytes(),
                "placed": b.sharding.is_equivalent_to(s, b.ndim),
                "split": len(b.sharding.device_set) == 4,
                "one_call_off": int((off > 0).sum()),
                "one_call_ulps": int(off.max()), "size": int(off.size)})
    return out


def main() -> int:
    import pytest

    import test_checks
    from chipbench.run import run_cell

    for seed in (5, 3000000000):
        emit(check="weights", seed=seed, leaves=weights_check(seed))
    for fault in (None, "_unchanged_round", "_half_batch_round"):
        with pytest.MonkeyPatch.context() as mp:
            if fault is not None:
                getattr(test_checks, fault)(mp)
            r = run_cell(CELL, 21, 1.0, False, rehearse=True, t_start=0.0)
        emit(check="run", fault=fault, correct=r["correct"],
             window_compiles=r["window_compiles"],
             count=r["device"]["count"], checks=r["checks"],
             metrics=sorted(r["metrics"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
