"""The readings that the check's limits are set from; not part of a run.

    python chipbench/calibrate.py --workload <cell> --seeds <n> --first-seed <s>

For each seed, in one process: the cell's first stack is made and stored
as a run stores it, one tile goes through the campaign entry, and its
stored outputs are compared twice, with the float32 reference (the sound
run: its largest reading over the seeds is the lower reading of each
number) and with the reference computed in bfloat16 (the control: its
smallest reading is the upper one).  The last line of standard output is
a JSON summary.
"""

import time

STARTED = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)

from chipbench import harness  # noqa: E402


def main(argv=None, *, root: Path = ROOT, require_chip: bool = True) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = harness.Cell(root, args.workload)

    import jax

    dev = jax.devices()[0]
    if require_chip and dev.platform != "tpu":
        raise harness.Refused(f"calibrate: no TPU ({dev.platform!r})")
    sys.path.insert(0, str(root / "src"))
    jax.config.update("jax_compilation_cache_dir", harness.cache_dir(root))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import jax.numpy as jnp

    from chipbench.scenes import Tile
    from repro.core import ChunkStore, Festivus, InMemoryObjectStore
    from repro.launch.cluster import ClusterConfig

    config, traffic, campaign = cell.config, cell.traffic, cell.campaign
    params = harness.scene_params(cell)
    icfg = campaign.imagery_config(config)
    rows = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        cs = ChunkStore(Festivus(InMemoryObjectStore()), "bucket")
        tile = Tile(params, seed, 0)
        clock = time.perf_counter()
        harness.store_stack(cs, "in/t0", harness.generated(tile), params,
                            config["chunk_px"], traffic["codec"])
        stored = time.perf_counter() - clock
        clock = time.perf_counter()
        produced = campaign.run_tile(
            cs, "in/t0", "out/w0", icfg,
            ClusterConfig(nodes=config["workers"], max_retries=0))["produced"]
        ran = time.perf_counter() - clock
        clock = time.perf_counter()
        sound = campaign.Check(config, jnp.float32).numbers(
            cs, "in/t0", "out/w0", tile, produced)
        checked = time.perf_counter() - clock
        control = campaign.Check(config, jnp.bfloat16).numbers(
            cs, "in/t0", "out/w0", tile, produced)
        rows.append({"seed": seed, "sound": sound, "control": control})
        print(f"seed {seed}: store {stored:.2f} s, tile {ran:.2f} s, "
              f"check {checked:.2f} s; sound {sound}; control {control}",
              flush=True)
        del cs
    summary = {
        "workload": cell.name, "seeds": [r["seed"] for r in rows],
        "lower": {n: max(r["sound"][n] for r in rows)
                  for n in campaign.NUMBERS},
        "upper": {n: min(r["control"][n] for r in rows)
                  for n in campaign.NUMBERS},
        "limits": config["limits"], "rows": rows,
        "seconds": time.monotonic() - STARTED,
    }
    print(json.dumps(summary), flush=True)
    return summary


if __name__ == "__main__":
    try:
        main()
    except harness.Refused as e:
        print(e.code, file=sys.stderr)
        sys.exit(2)
