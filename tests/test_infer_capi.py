"""C client inference API (reference: the pd_inference_api.h C surface,
SURVEY.md §2.6 — unverified): build the embedding shim with g++, compile
a REAL C client against it, and check its output against the Python
predictor. Skips cleanly when the embedding toolchain is unavailable."""
import os
import subprocess
import sys
import sysconfig

import numpy as np
import pytest

import paddle_tpu as paddle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CSRC = os.path.join(REPO, "csrc")

C_CLIENT = r"""
#include <pthread.h>
#include <stdio.h>
#include <stdlib.h>
#include "paddle_tpu_infer_capi.h"

struct CloneJob {
  PD_Predictor* pred;
  long long total;
  float* buf;
  int rc;
};

static void* run_clone(void* arg) {
  struct CloneJob* job = (struct CloneJob*)arg;
  int64_t shape[2] = {2, 8};
  float ones[16];
  for (int i = 0; i < 16; ++i) ones[i] = 1.0f;
  PD_Tensor* cin = PD_PredictorGetInputHandle(
      job->pred, PD_PredictorGetInputName(job->pred, 0));
  PD_TensorReshape(cin, 2, shape);
  PD_TensorCopyFromCpuFloat(cin, ones);
  if (PD_PredictorRun(job->pred) != 0) { job->rc = 1; return NULL; }
  PD_Tensor* cout = PD_PredictorGetOutputHandle(
      job->pred, PD_PredictorGetOutputName(job->pred, 0));
  PD_TensorCopyToCpuFloat(cout, job->buf);
  job->rc = 0;
  return NULL;
}

int main(int argc, char** argv) {
  PD_Config* cfg = PD_ConfigCreate();
  PD_ConfigSetModel(cfg, argv[1], NULL);
  PD_Predictor* pred = PD_PredictorCreate(cfg);
  if (!pred) { fprintf(stderr, "create: %s\n", PD_GetLastError()); return 2; }
  PD_ConfigDestroy(cfg);

  int n_in = PD_PredictorGetInputNum(pred);
  printf("inputs %d\n", n_in);

  /* 2x8 input filled with i*0.125 */
  float data[16];
  for (int i = 0; i < 16; ++i) data[i] = (float)i * 0.125f;
  int64_t shape[2] = {2, 8};
  PD_Tensor* in = PD_PredictorGetInputHandle(
      pred, PD_PredictorGetInputName(pred, 0));
  PD_TensorReshape(in, 2, shape);
  PD_TensorCopyFromCpuFloat(in, data);

  if (PD_PredictorRun(pred) != 0) {
    fprintf(stderr, "run: %s\n", PD_GetLastError());
    return 3;
  }
  PD_Tensor* out = PD_PredictorGetOutputHandle(
      pred, PD_PredictorGetOutputName(pred, 0));
  int nd = PD_TensorGetNumDims(out);
  int64_t oshape[8];
  PD_TensorGetShape(out, oshape);
  long long total = 1;
  for (int i = 0; i < nd; ++i) total *= oshape[i];
  float* obuf = (float*)malloc(sizeof(float) * total);
  PD_TensorCopyToCpuFloat(out, obuf);
  printf("out %d dims:", nd);
  for (int i = 0; i < nd; ++i) printf(" %lld", (long long)oshape[i]);
  printf("\n");
  for (long long i = 0; i < total; ++i) printf("%.6f\n", obuf[i]);

  /* per-thread clone: serve from a SECOND thread (the GIL must be
     parked by the library or this deadlocks) */
  PD_Predictor* clone = PD_PredictorClone(pred);
  struct CloneJob job;
  job.pred = clone;
  job.total = total;
  job.buf = (float*)malloc(sizeof(float) * total);
  pthread_t th;
  if (pthread_create(&th, NULL, run_clone, &job) != 0) return 4;
  if (pthread_join(th, NULL) != 0) return 4;
  if (job.rc != 0) { fprintf(stderr, "clone thread rc=%d\n", job.rc); return 4; }
  printf("CLONE\n");
  for (long long i = 0; i < total; ++i) printf("%.6f\n", job.buf[i]);
  float* cbuf = job.buf;

  free(obuf);
  free(cbuf);
  PD_PredictorDestroy(clone);
  PD_PredictorDestroy(pred);
  return 0;
}
"""


@pytest.fixture(scope="module")
def capi_lib(tmp_path_factory):
    build = tmp_path_factory.mktemp("capi")
    lib = build / "libpaddle_tpu_infer.so"
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR")
    ver = f"python{sys.version_info.major}.{sys.version_info.minor}"
    cmd = [
        "g++", "-O2", "-shared", "-fPIC",
        os.path.join(CSRC, "paddle_tpu_infer_capi.cc"),
        f"-I{inc}", f"-L{libdir}", f"-l{ver}", "-ldl", "-lm",
        "-o", str(lib),
    ]
    r = subprocess.run(cmd, capture_output=True)
    if r.returncode != 0:
        pytest.skip(f"embedding toolchain unavailable: {r.stderr.decode()[:400]}")
    return lib, libdir


def test_c_client_matches_python_predictor(tmp_path, capi_lib):
    lib, libdir = capi_lib
    from paddle_tpu.static import InputSpec

    paddle.seed(0)
    net = paddle.nn.Sequential(
        paddle.nn.Linear(8, 16), paddle.nn.ReLU(), paddle.nn.Linear(16, 4))
    net.eval()
    prefix = os.path.join(str(tmp_path), "model")
    paddle.jit.save(net, prefix, input_spec=[InputSpec([2, 8], "float32")])

    src = tmp_path / "client.c"
    src.write_text(C_CLIENT)
    exe = tmp_path / "client"
    r = subprocess.run(
        ["g++", "-O2", str(src), f"-I{CSRC}", f"-L{lib.parent}",
         "-lpaddle_tpu_infer", "-lpthread", "-o", str(exe)],
        capture_output=True)
    assert r.returncode == 0, r.stderr.decode()[:500]

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([REPO] + [p for p in sys.path if p])
    env["LD_LIBRARY_PATH"] = os.pathsep.join(
        [str(lib.parent), libdir, env.get("LD_LIBRARY_PATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([str(exe), prefix], capture_output=True, env=env,
                       timeout=300)
    assert r.returncode == 0, (r.stdout.decode()[-500:],
                               r.stderr.decode()[-1500:])
    lines = r.stdout.decode().splitlines()
    assert lines[0] == "inputs 1"
    assert lines[1].startswith("out 2 dims: 2 4")
    clone_at = lines.index("CLONE")
    got = np.asarray([float(v) for v in lines[2:clone_at]]).reshape(2, 4)
    got_clone = np.asarray(
        [float(v) for v in lines[clone_at + 1:]]).reshape(2, 4)

    x = (np.arange(16, dtype=np.float32) * 0.125).reshape(2, 8)
    ref = net(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    ref_clone = net(paddle.to_tensor(np.ones((2, 8), "f4"))).numpy()
    np.testing.assert_allclose(got_clone, ref_clone, rtol=1e-5, atol=1e-5)
