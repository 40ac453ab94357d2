"""fluid.DistributeTranspiler compat shim (ref
transpiler/distribute_transpiler.py:256): a 1.x-era PS script — build
program + minimize, transpile, run pserver role and trainer roles
through plain exe.run — ports unmodified and CONVERGES, params living
on the native PS server."""
import threading

import numpy as np

import paddle_tpu as pt
from paddle_tpu import fluid
from paddle_tpu import static


_ONEX_SCRIPT = r"""
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.environ["PT_REPO"])
import jax
jax.config.update("jax_platforms", "cpu")

import paddle_tpu as pt
from paddle_tpu import fluid
from paddle_tpu import static

role = os.environ["TRAINING_ROLE"]
trainer_id = int(os.environ.get("TRAINER_ID", "0"))
port = int(os.environ["PS_PORT"])
trainers = int(os.environ["TRAINERS"])
steps = int(os.environ.get("STEPS", "30"))

rng = np.random.RandomState(0)
true_w = rng.randn(8, 1).astype("f4")
xs = rng.randn(512, 8).astype("f4")
ys = xs @ true_w + 0.1

prog = static.Program()
startup = static.Program()
with static.program_guard(prog, startup):
    fluid.layers.reset_parameters()
    x = static.data("x", [None, 8], "float32")
    label = static.data("label", [None, 1], "float32")
    pred = fluid.layers.fc(x, size=1, name="fit")
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, label))
    fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)

t = fluid.DistributeTranspiler()
t.transpile(trainer_id, program=prog, pservers="127.0.0.1:%d" % port,
            trainers=trainers, sync_mode=True)
exe = static.Executor()
if role == "PSERVER":
    t._heartbeat_timeout_s = 3.0
    ep = "127.0.0.1:%d" % port
    exe.run(t.get_startup_program(ep))
    exe.run(t.get_pserver_program(ep))     # serves, then returns
    print(json.dumps({"server_done": True}))
else:
    trainer_prog = t.get_trainer_program()
    lname = prog.recorder.name_of(loss)
    rw = np.random.RandomState(trainer_id)
    losses = []
    try:
        for _ in range(steps):
            idx = rw.randint(0, len(xs), 64)
            (lv,) = exe.run(trainer_prog,
                            feed={"x": xs[idx], "label": ys[idx]},
                            fetch_list=[lname])
            losses.append(float(lv))
    finally:
        trainer_prog.complete()
    print(json.dumps({"trainer": trainer_id, "losses": losses}))
"""


def _onex_style_ps_script(port, trainers=2, steps=30):
    """The reference's dist fit-a-line shape: y = xW+b, sgd minimize,
    DistributeTranspiler roles — ONE role per PROCESS, exactly how 1.x
    PS scripts deploy (TRAINING_ROLE env). Threads in one process would
    share the fluid name-scoped parameter registry and race on the
    Executor's donated buffers."""
    import json
    import os
    import subprocess
    import sys
    import tempfile
    import time

    script = os.path.join(tempfile.mkdtemp(), "onex_ps.py")
    with open(script, "w") as f:
        f.write(_ONEX_SCRIPT)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def spawn(role, tid=0):
        env = dict(os.environ)
        env.update(PT_REPO=repo, TRAINING_ROLE=role, TRAINER_ID=str(tid),
                   PS_PORT=str(port), TRAINERS=str(trainers),
                   STEPS=str(steps), JAX_PLATFORMS="cpu")
        return subprocess.Popen([sys.executable, script],
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True,
                                env=env)

    server = spawn("PSERVER")
    time.sleep(1.0)
    workers = [spawn("TRAINER", i) for i in range(trainers)]
    results = {}
    for i, p in enumerate(workers):
        out, err = p.communicate(timeout=420)
        assert p.returncode == 0, f"trainer{i} rc={p.returncode}: {err[-800:]}"
        rec = json.loads(out.strip().splitlines()[-1])
        results[f"trainer{rec['trainer']}"] = rec["losses"]
    out, err = server.communicate(timeout=60)
    assert server.returncode == 0, f"pserver rc={server.returncode}: {err[-800:]}"
    results.update(json.loads(out.strip().splitlines()[-1]))
    return results


def test_onex_ps_script_converges():
    import os
    port = 40600 + os.getpid() % 1000
    r = _onex_style_ps_script(port)
    assert r.get("server_done"), "pserver never finished serving"
    for tid in (0, 1):
        losses = r[f"trainer{tid}"]
        assert losses[-1] < losses[0] * 0.2, (tid, losses[::8])


def test_transpile_requires_params():
    prog = static.Program()
    with static.program_guard(prog):
        static.data("x", [None, 4], "float32")
    t = fluid.DistributeTranspiler()
    import pytest
    with pytest.raises(ValueError, match="persistable"):
        t.transpile(0, program=prog, pservers="127.0.0.1:1", trainers=1)


def test_multi_pserver_rejected_with_guidance():
    prog = static.Program()
    with static.program_guard(prog):
        fluid.layers.reset_parameters()
        x = static.data("x", [None, 4], "float32")
        fluid.layers.fc(x, size=2)
    t = fluid.DistributeTranspiler(
        config=fluid.DistributeTranspilerConfig())
    import pytest
    with pytest.raises(NotImplementedError, match="fleet"):
        t.transpile(0, program=prog,
                    pservers="127.0.0.1:1,127.0.0.1:2", trainers=2)
