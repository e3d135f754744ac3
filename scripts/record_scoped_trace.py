"""Record the small TPU trace that ``tests/test_profiler_reader.py`` pins
(``tests/data/scoped_steps.xplane.pb``): two jitted programs with scopes
of the vocabulary, a ``while`` body and a backward pass, under the
program's own host spans. On the chip:

    chiprun -- python scripts/record_scoped_trace.py

writes ``chiprun_out/scoped_steps.xplane.pb`` and prints the reader's
tables; copy the file to ``tests/data/`` and re-pin the test's sums.
"""
import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.profiler import (Profiler, RecordEvent,  # noqa: E402
                                 load_profiler_result)


def decode(x, w):
    with jax.named_scope("attn.proj"):
        h = x @ w

    def body(_, h):
        with jax.named_scope("mlp"):
            return jnp.tanh(h @ w)

    h = jax.lax.fori_loop(0, 3, body, h)
    with jax.named_scope("head"):
        logits = h @ w.T
    with jax.named_scope("sample"):
        return jnp.argmax(logits, axis=-1)


def train(w, x):
    def loss_of(w):
        with jax.named_scope("mlp"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("loss"):
            return jnp.mean(jnp.square(h.astype(jnp.float32)))

    loss, g = jax.value_and_grad(loss_of)(w)
    with jax.named_scope("optimizer"):
        return loss, w - 0.01 * g.astype(w.dtype)


def main():
    out = os.path.join(ROOT, "chiprun_out")
    log = os.path.join(out, "scoped_trace")
    shutil.rmtree(log, ignore_errors=True)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.full((1024, 1024), 0.01, jnp.bfloat16)
    quantum, step = jax.jit(decode), jax.jit(train)
    quantum(x, w).block_until_ready()
    jax.block_until_ready(step(w, x))
    with Profiler(log_dir=log):
        for _ in range(3):
            with RecordEvent("door.pump"):
                with RecordEvent("engine.step"):
                    with RecordEvent("engine.decode.enqueue"):
                        toks = quantum(x, w)
                    with RecordEvent("engine.decode.sync"):
                        toks.block_until_ready()
            time.sleep(0.002)
        with RecordEvent("train.run_steps"):
            with RecordEvent("train.enqueue"):
                loss, w2 = step(w, x)
        jax.block_until_ready((loss, w2))
    path = glob.glob(os.path.join(log, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    dest = os.path.join(out, "scoped_steps.xplane.pb")
    shutil.copy(path, dest)
    print("bytes", os.path.getsize(dest))
    print(load_profiler_result(dest).tables())


if __name__ == "__main__":
    main()
