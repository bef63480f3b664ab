"""Standalone serving from the exported artifact.

Process A defines a model class, jit.saves it with input_spec, and records
expected outputs. Process B — which has NO access to the model class — loads
via create_predictor(Config(path)) and must reproduce the numerics from the
serialized artifact alone (reference capability: predictor-from-file,
analysis_predictor.h:105).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SAVER = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, os.environ["PADDLE_TPU_REPO"])
    # pin CPU like every other spawned worker: the suite never needs a chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import paddle_tpu as P
    from paddle_tpu import nn

    class SecretModel(nn.Layer):  # exists ONLY in this process
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(8, 16)
            self.fc2 = nn.Linear(16, 3)

        def forward(self, x):
            return self.fc2(P.nn.functional.gelu(self.fc1(x)))

    P.seed(11)
    m = SecretModel()
    m.eval()
    x = np.random.RandomState(5).randn(4, 8).astype(np.float32)
    out = m(P.to_tensor(x)).numpy()
    d = sys.argv[1]
    P.jit.save(m, os.path.join(d, "model"),
               input_spec=[P.static.InputSpec([4, 8], "float32")])
    np.save(os.path.join(d, "x.npy"), x)
    np.save(os.path.join(d, "expected.npy"), out)
    meta = json.load(open(os.path.join(d, "model.pdmodel.json")))
    assert "stablehlo_error" not in meta, meta.get("stablehlo_error")
    assert os.path.exists(os.path.join(d, "model.jaxexport"))
    assert os.path.exists(os.path.join(d, "model.stablehlo"))
""")

SERVER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, os.environ["PADDLE_TPU_REPO"])
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    from paddle_tpu.inference import Config, PredictorPool, create_predictor

    d = sys.argv[1]
    x = np.load(os.path.join(d, "x.npy"))
    expected = np.load(os.path.join(d, "expected.npy"))

    config = Config(os.path.join(d, "model"))
    pred = create_predictor(config)
    # handles API (ZeroCopyTensor style)
    names = pred.get_input_names()
    pred.get_input_handle(names[0]).copy_from_cpu(x)
    outs = pred.run()
    np.testing.assert_allclose(outs[0], expected, rtol=1e-4, atol=1e-5)

    # PredictorPool serves the same artifact from several predictors
    pool = PredictorPool(config, size=2)
    for i in range(2):
        o = pool.retrieve(i).run([x])
        np.testing.assert_allclose(o[0], expected, rtol=1e-4, atol=1e-5)
    print("SERVED_OK")
""")


def test_serve_artifact_without_model_class(tmp_path):
    env = dict(os.environ)
    env["PADDLE_TPU_REPO"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    saver = tmp_path / "saver.py"
    saver.write_text(SAVER)
    r = subprocess.run([sys.executable, str(saver), str(tmp_path)],
                       capture_output=True, text=True, timeout=180, env=env)
    assert r.returncode == 0, r.stderr[-2000:]

    server = tmp_path / "server.py"
    server.write_text(SERVER)
    r2 = subprocess.run([sys.executable, str(server), str(tmp_path)],
                        capture_output=True, text=True, timeout=180, env=env)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "SERVED_OK" in r2.stdout
