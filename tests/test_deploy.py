"""Deployment artifact: construction, persistence, serving from disk.

The correctness bar is the ISSUE-5 acceptance line: `Deployment.save`
-> `load` -> `run` round-trips BIT-EXACTLY — on all three logical bank
configurations of the macro AND a conv config, for the noiseless spec
and the per-request-key silicon spec — and `serve.picbnn` registers
models from a live Deployment, and from a checkpoint directory, serving
the same bits either way.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bnn, convnet, ensemble
from repro.core.binarize import InputEncoding
from repro.core.convnet import CNNConfig, ConvSpec
from repro.core.device_model import NOISELESS, SILICON
from repro.deploy import COMPILE_OPTIONS, Deployment, deploy, is_deployment_dir
from repro.serve.picbnn import BatchingPolicy, PicBnnServer
from repro.spec import InferenceSpec

BANK_NETS = {
    "512x256": (300, 192, 12),
    "1024x128": (784, 64, 10),
    "2048x64": (96, 32, 5),
}
BANK_BIAS = {"512x256": 64, "1024x128": 64, "2048x64": 32}

#: small end-to-end-binary CNN (12x12 input) — fast but exercises the
#: conv prefix, thermometer encoding, and positionwise FC repack
TINY_CNN = CNNConfig(
    side=12,
    encoding=InputEncoding("thermometer", 4),
    conv=(ConvSpec(3, 32, 2),),
    hidden=(64,),
    n_classes=5,
    bias_cells=64,
)

VOTES = InferenceSpec()
EACH = InferenceSpec(noise="per_request")


def _random_folded(sizes, seed, bias_cells):
    rng = np.random.default_rng(seed)
    layers = []
    for i in range(len(sizes) - 1):
        n_in, n_out = sizes[i], sizes[i + 1]
        c = bnn.parity_adjust_c(
            rng.integers(-bias_cells, bias_cells + 1, n_out), n_in, bias_cells
        )
        layers.append(bnn.FoldedLayer(
            weights_pm1=rng.choice([-1, 1], (n_out, n_in)).astype(np.int8),
            c=c,
        ))
    return layers


def _mlp_deployment(bank, noise=None, **opts):
    sizes, bias = BANK_NETS[bank], BANK_BIAS[bank]
    folded = _random_folded(sizes, seed=sum(map(ord, bank)), bias_cells=bias)
    return deploy(
        folded, ens_cfg=ensemble.EnsembleConfig(bias_cells=bias),
        noise=noise, min_bucket=8, **opts
    ), sizes


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------
def test_deploy_from_folded_and_layer_sizes():
    dep, sizes = _mlp_deployment("1024x128")
    assert dep.layer_sizes == sizes
    assert dep.conv_layers == ()
    pipe = dep.pipeline()
    assert pipe is dep.pipeline()  # compiled once, cached
    assert (pipe.n_in, pipe.n_classes) == (sizes[0], sizes[-1])


def test_deploy_from_trained_params_folds_here():
    cfg = bnn.MLPConfig(layer_sizes=(64, 32, 4), bias_cells=32)
    params = bnn.init_params(jax.random.PRNGKey(0), cfg)
    dep = deploy(params, config=cfg, min_bucket=8)
    # config supplies the ensemble bias cells; fold ran inside deploy()
    assert dep.ens_cfg.bias_cells == 32
    assert dep.layer_sizes == (64, 32, 4)
    want = deploy(bnn.fold(params, cfg), config=cfg, min_bucket=8)
    x = np.random.default_rng(1).choice([-1.0, 1.0], (5, 64)).astype(
        np.float32)
    np.testing.assert_array_equal(
        np.asarray(dep.run(x, VOTES)), np.asarray(want.run(x, VOTES))
    )


def test_deploy_cnn_config_threads_geometry():
    folded = convnet.random_folded_cnn(TINY_CNN, seed=3)
    dep = deploy(folded, config=TINY_CNN, min_bucket=4)
    assert dep.image_side == TINY_CNN.side
    assert dep.image_encoding == TINY_CNN.encoding
    assert dep.layer_sizes is None  # conv graphs have no MLP topology
    assert len(dep.conv_layers) == 1
    pipe = dep.pipeline()
    assert pipe.n_in == TINY_CNN.side ** 2


def test_deploy_rejects_unknown_options_and_dict_without_config():
    folded = _random_folded((64, 4), seed=1, bias_cells=32)
    with pytest.raises(ValueError, match="unknown compile options"):
        deploy(folded, block_size=4)
    with pytest.raises(ValueError, match="config="):
        deploy({"layers": []})
    assert "impl" not in COMPILE_OPTIONS


@pytest.mark.parametrize("option", ["interpret", "chunk", "impl"])
def test_deploy_rejects_backend_decided_options(option):
    """How a kernel runs, and whether the kernel or the XLA twin runs at
    all, are derived from the backend and the graph: a manifest cannot
    carry interpret mode, the old word-chunk tile or a forced
    implementation onto a TPU."""
    folded = _random_folded((64, 4), seed=1, bias_cells=32)
    assert option not in COMPILE_OPTIONS
    with pytest.raises(ValueError, match="unknown compile options"):
        deploy(folded, **{option: True})


# ---------------------------------------------------------------------------
# save / load round trips (the acceptance bar)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bank", sorted(BANK_NETS))
def test_save_load_bit_exact_all_banks(bank, tmp_path):
    """Noiseless spec AND per-request silicon spec survive the disk
    round trip bit-for-bit, on every logical bank configuration."""
    dep, sizes = _mlp_deployment(bank, noise=SILICON)
    rng = np.random.default_rng(7)
    x = rng.choice([-1.0, 1.0], (13, sizes[0])).astype(np.float32)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(11), 13))
    want_nl = np.asarray(dep.run(x, VOTES))
    want_si = np.asarray(dep.run(x, EACH, keys=keys))

    root = tmp_path / bank
    dep.save(root)
    assert is_deployment_dir(root)
    loaded = Deployment.load(root)
    assert loaded.noise == SILICON
    assert loaded.ens_cfg == dep.ens_cfg
    assert loaded.compile_options == dep.compile_options
    for orig, back in zip(dep.folded, loaded.folded):
        np.testing.assert_array_equal(orig.weights_pm1, back.weights_pm1)
        np.testing.assert_array_equal(orig.c, back.c)
    np.testing.assert_array_equal(np.asarray(loaded.run(x, VOTES)), want_nl)
    np.testing.assert_array_equal(
        np.asarray(loaded.run(x, EACH, keys=keys)), want_si
    )


@pytest.mark.parametrize("cfg_name", ["tiny", "mnist_cnn"])
def test_save_load_bit_exact_cnn(cfg_name, tmp_path):
    """The conv configs round-trip too: conv prefix (shapes + strides),
    input encoding, and image geometry all reconstruct from disk — on a
    fast tiny config AND the paper's MNIST CNN config."""
    if cfg_name == "mnist_cnn":
        from repro.configs.paper_cnn import MNIST_CNN as cfg
    else:
        cfg = TINY_CNN
    folded = convnet.random_folded_cnn(cfg, seed=5)
    dep = deploy(folded, config=cfg, noise=SILICON, min_bucket=4)
    rng = np.random.default_rng(9)
    x = rng.random((6, cfg.n_in)).astype(np.float32)  # raw pixels
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(2), 6))
    want_nl = np.asarray(dep.run(x, VOTES))
    want_si = np.asarray(dep.run(x, EACH, keys=keys))

    dep.save(tmp_path / "cnn")
    loaded = Deployment.load(tmp_path / "cnn")
    assert loaded.image_side == cfg.side
    assert loaded.image_encoding == cfg.encoding
    conv0 = loaded.conv_layers[0]
    assert conv0.stride == cfg.conv[0].stride
    assert conv0.weights_pm1.shape == dep.conv_layers[0].weights_pm1.shape
    np.testing.assert_array_equal(np.asarray(loaded.run(x, VOTES)), want_nl)
    np.testing.assert_array_equal(
        np.asarray(loaded.run(x, EACH, keys=keys)), want_si
    )


def test_save_load_noiseless_and_calibrated_config(tmp_path):
    """noise=None round-trips as None; a noiseless-physics deployment
    keeps its NOISELESS model; non-default ensemble fields survive."""
    dep, sizes = _mlp_deployment("2048x64")
    dep.save(tmp_path / "plain")
    assert Deployment.load(tmp_path / "plain").noise is None

    nl, _ = _mlp_deployment("2048x64", noise=NOISELESS)
    nl.save(tmp_path / "nl")
    back = Deployment.load(tmp_path / "nl")
    assert back.noise == NOISELESS and back.noise is not None

    # a NON-default ens_cfg.noise field round-trips too (the pipeline
    # ignores it — physics come from Deployment.noise — but
    # load(save(d)).ens_cfg must equal d.ens_cfg field for field)
    sizes, bias = BANK_NETS["2048x64"], BANK_BIAS["2048x64"]
    folded = _random_folded(sizes, seed=1, bias_cells=bias)
    ec = ensemble.EnsembleConfig(bias_cells=bias, noise=SILICON)
    dep = deploy(folded, ens_cfg=ec, min_bucket=8)
    dep.save(tmp_path / "ecn")
    assert Deployment.load(tmp_path / "ecn").ens_cfg == ec


def test_load_rejects_non_deployment_dirs(tmp_path):
    with pytest.raises(FileNotFoundError, match="deployment.json"):
        Deployment.load(tmp_path / "missing")
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "deployment.json").write_text('{"schema": "other/v9"}')
    with pytest.raises(ValueError, match="schema"):
        Deployment.load(bad)
    assert not is_deployment_dir(tmp_path / "missing")


# ---------------------------------------------------------------------------
# serving: register from a live Deployment and from a checkpoint path
# ---------------------------------------------------------------------------
def test_server_registers_deployment_and_checkpoint_path(tmp_path):
    dep, sizes = _mlp_deployment("2048x64", max_bucket=32)
    si, _ = _mlp_deployment("2048x64", noise=SILICON, max_bucket=32)
    si.save(tmp_path / "si")

    x = np.random.default_rng(3).choice(
        [-1.0, 1.0], (17, sizes[0])).astype(np.float32)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(4), len(x)))
    want_nl = np.asarray(dep.run(x, VOTES))
    want_si = np.asarray(si.run(x, EACH, keys=keys))

    srv = PicBnnServer(BatchingPolicy(max_batch=8, max_wait_us=200.0))
    srv.register("live", dep)  # live Deployment (layer_sizes derived)
    srv.register("disk", str(tmp_path / "si"))  # checkpoint directory
    with srv:
        hs_nl = [srv.submit("live", x[i]) for i in range(len(x))]
        hs_si = [srv.submit("disk", x[i], key=keys[i])
                 for i in range(len(x))]
        got_nl = np.stack([h.result(timeout=60).votes for h in hs_nl])
        got_si = np.stack([h.result(timeout=60).votes for h in hs_si])
    np.testing.assert_array_equal(got_nl, want_nl)
    np.testing.assert_array_equal(got_si, want_si)
    st = srv.stats()
    # layer_sizes derived from the MLP deployment -> Table-II equivalent
    assert st.per_model["live"].silicon_inf_per_s > 0


def test_server_warmup_reports_spec_attribution():
    dep, _sizes = _mlp_deployment("2048x64", noise=SILICON, max_bucket=16)
    srv = PicBnnServer(BatchingPolicy(max_batch=16, max_wait_us=200.0))
    srv.register("m", dep, mc_samples=2)
    report = srv.warmup()
    spec = InferenceSpec(noise="per_request", mc_samples=2,
                         reduction="sum")
    assert set(report) == {"m"}
    assert set(report["m"]) == {(spec, 8), (spec, 16)}
    assert all(t > 0 for t in report["m"].values())
