"""Fault tolerance + elasticity demo.

  PYTHONPATH=src python examples/elastic_restart.py

1. trains with a checkpoint every 4 steps,
2. injects a simulated node failure mid-run — the Supervisor restores the
   latest checkpoint (params, optimizer, data-iterator position) and
   resumes; final losses are identical to a failure-free run,
3. then restores the same checkpoint onto a DIFFERENT mesh layout
   (elastic restart: e.g. a job rescheduled on fewer chips),
4. finally, the SERVING side: a whole augmented array is lost mid-decode
   — the engine's Supervisor drains the in-flight rows, requeues them
   from their prompts + already-emitted tokens, and the finished streams
   are token-identical to a loss-free run.
"""
import dataclasses
import shutil

import jax
import numpy as np
from jax.sharding import AxisType

from repro import checkpoint as ckpt_lib
from repro.configs import get_arch
from repro.configs.base import AMCConfig, ShapeConfig
from repro.distributed.fault import SimulatedFailure
from repro.launch.mesh import make_local_mesh
from repro.serve import Request, ServeEngine
from repro.train import TrainSettings
from repro.train.trainer import Trainer, TrainerConfig

CKPT = "/tmp/amc_elastic_demo"
shutil.rmtree(CKPT, ignore_errors=True)
shutil.rmtree(CKPT + "_clean", ignore_errors=True)

cfg = get_arch("qwen1.5-0.5b").reduced()
shape = ShapeConfig("t", 64, 4, "train")
settings = TrainSettings(lr=5e-3, q_chunk=16)

fired = {"done": False}


def injector(step):
    if step == 6 and not fired["done"]:
        fired["done"] = True
        raise SimulatedFailure("pod 1 lost heartbeat")


tr = Trainer(cfg, shape, make_local_mesh(), settings,
             TrainerConfig(total_steps=12, ckpt_every=4, ckpt_dir=CKPT,
                           warmup=2),
             failure_injector=injector)
losses = tr.train()
tr.close()
print(f"run with failure @6: restarts={tr.supervisor.restarts}, "
      f"{len(losses)} losses, final={losses[-1]:.4f}")

tr2 = Trainer(cfg, shape, make_local_mesh(), settings,
              TrainerConfig(total_steps=12, ckpt_every=4,
                            ckpt_dir=CKPT + "_clean", warmup=2))
losses_clean = tr2.train()
tr2.close()
assert np.allclose(losses, losses_clean, rtol=1e-5), "recovery diverged!"
print("failure-free run matches exactly: recovery lost/repeated no steps")

# elastic restore: same checkpoint, different mesh (here 1 device x (1,1) —
# on a pod this is e.g. 512 -> 256 chips; arrays are saved as full logical
# values and re-laid-out by device_put)
step = ckpt_lib.latest_step(CKPT)
mesh2 = jax.make_mesh((1, 1), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
tr3 = Trainer(cfg, shape, mesh2, settings,
              TrainerConfig(total_steps=12, ckpt_dir=CKPT, warmup=2))
print(f"elastic restore at step {tr3.current_step()} onto mesh "
      f"{dict(mesh2.shape)}: OK")
tr3.close()

# --- serving array-loss recovery -------------------------------------------
# lose a whole augmented SRAM array mid-decode; the engine's Supervisor
# preempts every in-flight row (the dynamic plane is gone) and requeues
# each request from prompt + tokens already emitted — greedy decode makes
# the recovered streams bit-identical to a loss-free run.
scfg = dataclasses.replace(
    get_arch("qwen1.5-0.5b").reduced(),
    amc=AMCConfig(pool_mode="always-augmented", kv_mode="int4"))
smesh = make_local_mesh()
rng = np.random.default_rng(0)
prompts = [rng.integers(0, scfg.vocab, size=(20,)).astype(np.int32)
           for _ in range(3)]


def serve_reqs():
    return [Request(prompt=p, max_new_tokens=6, id=i)
            for i, p in enumerate(prompts)]


golden = ServeEngine(scfg, smesh, max_batch=2, max_seq=64,
                     prefill_chunk=16).generate(serve_reqs())

eng = ServeEngine(scfg, smesh, max_batch=2, max_seq=64, prefill_chunk=16)
for r in serve_reqs():
    eng.add_request(r)
eng.step_all()
eng.step_all()
eng.inject_array_loss()          # the whole dynamic plane, gone
while eng.active.any() or eng._queue:
    eng.step_all()
fl = eng.stats()["faults"]
assert all(np.array_equal(golden[i], eng.outputs[i]) for i in golden), \
    "array-loss recovery diverged!"
print(f"serving array loss @step2: requeued={fl['array_loss_requeues']} "
      f"restarts={fl['supervisor_restarts']}, recovered streams "
      f"token-identical to loss-free run")
