"""Cross-framework numerical parity: a real torch ResNet/ViT (the
reference's model family, ``imagenet.py:312``) and our Flax model must
produce the SAME logits when our model consumes the converted torch
state_dict (``compat/torch_weights.py``) — the strongest architecture
equivalence check available without the dataset (torchvision itself is
not in the image, so the torch reference is built here with the same
block plan torchvision uses)."""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
import torch.nn as tnn  # noqa: E402

import jax  # noqa: E402

from imagent_tpu.compat import resnet_from_torch, vit_from_torch  # noqa: E402
from imagent_tpu.models import create_model  # noqa: E402
from imagent_tpu.models.vit import VisionTransformer  # noqa: E402


# ---- torch reference models (torchvision block plan, plain torch) ----

class TorchBasicBlock(tnn.Module):
    def __init__(self, cin, cout, stride=1):
        super().__init__()
        self.conv1 = tnn.Conv2d(cin, cout, 3, stride, 1, bias=False)
        self.bn1 = tnn.BatchNorm2d(cout)
        self.conv2 = tnn.Conv2d(cout, cout, 3, 1, 1, bias=False)
        self.bn2 = tnn.BatchNorm2d(cout)
        self.downsample = None
        if stride != 1 or cin != cout:
            self.downsample = tnn.Sequential(
                tnn.Conv2d(cin, cout, 1, stride, bias=False),
                tnn.BatchNorm2d(cout))

    def forward(self, x):
        idn = x if self.downsample is None else self.downsample(x)
        y = self.bn1(self.conv1(x)).relu()
        y = self.bn2(self.conv2(y))
        return (y + idn).relu()


class TorchResNet18(tnn.Module):
    def __init__(self, num_classes=10):
        super().__init__()
        self.conv1 = tnn.Conv2d(3, 64, 7, 2, 3, bias=False)
        self.bn1 = tnn.BatchNorm2d(64)
        self.maxpool = tnn.MaxPool2d(3, 2, 1)
        chans = [64, 64, 128, 256, 512]
        for i in range(4):
            blocks = [TorchBasicBlock(chans[i], chans[i + 1],
                                      stride=1 if i == 0 else 2),
                      TorchBasicBlock(chans[i + 1], chans[i + 1])]
            setattr(self, f"layer{i + 1}", tnn.Sequential(*blocks))
        self.fc = tnn.Linear(512, num_classes)

    def forward(self, x):
        x = self.maxpool(self.bn1(self.conv1(x)).relu())
        for i in range(4):
            x = getattr(self, f"layer{i + 1}")(x)
        return self.fc(x.mean(dim=(2, 3)))


def _randomize_bn_stats(model):
    """Non-trivial running stats so a mean/var mapping error can't hide."""
    g = torch.Generator().manual_seed(7)
    for m in model.modules():
        if isinstance(m, tnn.BatchNorm2d):
            m.running_mean.copy_(
                torch.randn(m.running_mean.shape, generator=g) * 0.1)
            m.running_var.copy_(
                torch.rand(m.running_var.shape, generator=g) + 0.5)


def test_resnet18_logits_match_torch():
    torch.manual_seed(0)
    tm = TorchResNet18(num_classes=10).eval()
    with torch.no_grad():
        _randomize_bn_stats(tm)

    params, stats = resnet_from_torch(tm.state_dict(), (2, 2, 2, 2))
    fm = create_model("resnet18", num_classes=10)

    x = np.random.default_rng(1).normal(
        size=(4, 3, 64, 64)).astype(np.float32)
    with torch.no_grad():
        want = tm(torch.from_numpy(x)).numpy()
    got = np.asarray(fm.apply(
        {"params": params, "batch_stats": stats},
        np.transpose(x, (0, 2, 3, 1)), train=False))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


class TorchViTBlock(tnn.Module):
    def __init__(self, d, heads, mlp):
        super().__init__()
        self.ln_1 = tnn.LayerNorm(d, eps=1e-6)
        self.self_attention = tnn.MultiheadAttention(d, heads,
                                                     batch_first=True)
        self.ln_2 = tnn.LayerNorm(d, eps=1e-6)
        self.mlp = tnn.Sequential(tnn.Linear(d, mlp), tnn.GELU(),
                                  tnn.Identity(), tnn.Linear(mlp, d))

    def forward(self, x):
        y = self.ln_1(x)
        x = x + self.self_attention(y, y, y, need_weights=False)[0]
        return x + self.mlp(self.ln_2(x))


class TorchViT(tnn.Module):
    """torchvision vit plan: patch conv, class token, pos emb, pre-LN
    encoder, LN, linear head. State-dict keys follow torchvision naming
    so the converter sees the real layout."""

    def __init__(self, d=64, heads=4, mlp=128, layers=2, patch=8,
                 image=32, classes=10):
        super().__init__()
        n = (image // patch) ** 2 + 1
        self.conv_proj = tnn.Conv2d(3, d, patch, patch)
        self.class_token = tnn.Parameter(torch.zeros(1, 1, d))
        enc_layers = {f"encoder_layer_{i}": TorchViTBlock(d, heads, mlp)
                      for i in range(layers)}
        self.encoder = tnn.Module()
        self.encoder.pos_embedding = tnn.Parameter(
            torch.empty(1, n, d).normal_(std=0.02))
        self.encoder.layers = tnn.ModuleDict(enc_layers)
        self.encoder.ln = tnn.LayerNorm(d, eps=1e-6)
        self.heads = tnn.Module()
        self.heads.head = tnn.Linear(d, classes)

    def forward(self, x):
        b = x.shape[0]
        x = self.conv_proj(x).flatten(2).transpose(1, 2)  # [B, N, D]
        x = torch.cat([self.class_token.expand(b, -1, -1), x], dim=1)
        x = x + self.encoder.pos_embedding
        for blk in self.encoder.layers.values():
            x = blk(x)
        x = self.encoder.ln(x)
        return self.heads.head(x[:, 0])


def test_vit_logits_match_torch():
    torch.manual_seed(3)
    tm = TorchViT().eval()
    with torch.no_grad():
        tm.class_token.normal_(std=0.02)

    # ModuleDict keys serialize as encoder.layers.encoder_layer_i.*
    params = vit_from_torch(tm.state_dict(), num_heads=4)
    fm = VisionTransformer(patch_size=8, hidden_dim=64, num_layers=2,
                           num_heads=4, mlp_dim=128, num_classes=10)

    x = np.random.default_rng(2).normal(
        size=(4, 3, 32, 32)).astype(np.float32)
    with torch.no_grad():
        want = tm(torch.from_numpy(x)).numpy()
    got = np.asarray(fm.apply(
        {"params": params, "batch_stats": {}},
        np.transpose(x, (0, 2, 3, 1)), train=False))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_vit_to_torch_roundtrip():
    """Export inverts import bit-exactly — the QKV per-head kernels
    re-fuse into in_proj_weight in torchvision's [q; k; v] row order —
    and the exported dict loads into a FRESH torch ViT reproducing the
    Flax logits (train-here/serve-in-torch for the third family)."""
    from imagent_tpu.compat import vit_to_torch

    torch.manual_seed(7)
    tm = TorchViT().eval()
    with torch.no_grad():
        tm.class_token.normal_(std=0.02)
    sd0 = {k: v.numpy() for k, v in tm.state_dict().items()}

    params = vit_from_torch(sd0, num_heads=4)
    sd1 = vit_to_torch(params)
    assert set(sd1) == set(sd0)
    for k, v in sd0.items():
        np.testing.assert_array_equal(sd1[k], v, err_msg=k)

    tm2 = TorchViT().eval()
    tm2.load_state_dict({k: torch.from_numpy(np.asarray(v).copy())
                         for k, v in sd1.items()})
    fm = VisionTransformer(patch_size=8, hidden_dim=64, num_layers=2,
                           num_heads=4, mlp_dim=128, num_classes=10)
    x = np.random.default_rng(11).normal(
        size=(4, 3, 32, 32)).astype(np.float32)
    with torch.no_grad():
        want = tm2(torch.from_numpy(x)).numpy()
    got = np.asarray(fm.apply(
        {"params": params, "batch_stats": {}},
        np.transpose(x, (0, 2, 3, 1)), train=False))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_engine_export_torch(tmp_path):
    """--export-torch end-to-end: a training run (with EMA on, so the
    export must carry the EMA weights the reported metrics were
    evaluated on) writes a torchvision-named .pt; a real torch ResNet
    loads it strict=True (minus num_batches_tracked), and
    --init-from-torch round-trips it back into an --eval-only run
    (EMA off: imported params evaluated directly) reproducing the val
    metrics — the full CLI-level train-here/serve-in-torch loop."""
    from imagent_tpu.config import Config
    from imagent_tpu.engine import run

    pt = tmp_path / "exported.pt"
    cfg = Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                 batch_size=4, epochs=1, lr=0.01, dataset="synthetic",
                 synthetic_size=32, workers=0, bf16=False, log_every=0,
                 ema_decay=0.5, export_torch=str(pt),
                 log_dir=str(tmp_path / "tb"),
                 ckpt_dir=str(tmp_path / "ckpt"))
    result = run(cfg)
    assert pt.exists()

    sd = torch.load(pt, weights_only=True)
    tm = TorchResNet18(num_classes=4)
    missing, unexpected = tm.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    assert all(k.endswith("num_batches_tracked") for k in missing), missing

    # Round-trip: the exported file feeds --init-from-torch --eval-only
    # and reproduces the val metrics of the run that exported it (which
    # were EMA-evaluated — matching proves the EMA weights shipped).
    cfg2 = cfg.replace(export_torch="", init_from_torch=str(pt),
                       eval_only=True, ema_decay=0.0,
                       log_dir=str(tmp_path / "tb2"),
                       ckpt_dir=str(tmp_path / "ckpt2"))
    result2 = run(cfg2)
    np.testing.assert_allclose(result2["final_val"]["top1"],
                               result["final_val"]["top1"], atol=1e-6)
    np.testing.assert_allclose(result2["final_val"]["loss"],
                               result["final_val"]["loss"], rtol=1e-5)


def test_engine_init_from_torch(tmp_path):
    """--init-from-torch end-to-end: the reference's DDP-prefixed .pt
    loads into a training run; wrong arch fails loudly."""
    from imagent_tpu.config import Config
    from imagent_tpu.engine import run

    torch.manual_seed(5)
    tm = TorchResNet18(num_classes=4)
    # The reference saves the DDP-wrapped model: "module." prefix
    # (imagenet.py:316,392).
    sd = {f"module.{k}": v for k, v in tm.state_dict().items()}
    pt = tmp_path / "imagenet_FR_resnet18.pt"
    torch.save(sd, pt)

    cfg = Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                 batch_size=4, epochs=1, lr=0.01, dataset="synthetic",
                 synthetic_size=32, workers=0, bf16=False, log_every=0,
                 init_from_torch=str(pt), log_dir=str(tmp_path / "tb"),
                 ckpt_dir=str(tmp_path / "ckpt"))
    result = run(cfg)
    assert result["final_train"]["n"] == 32

    bad = cfg.replace(num_classes=8)
    with pytest.raises(ValueError, match="shape mismatch"):
        run(bad)


class TorchBottleneck(tnn.Module):
    """torchvision v1.5 bottleneck: 1x1 -> 3x3(stride) -> 1x1, expansion 4."""

    def __init__(self, cin, planes, stride=1):
        super().__init__()
        cout = planes * 4
        self.conv1 = tnn.Conv2d(cin, planes, 1, bias=False)
        self.bn1 = tnn.BatchNorm2d(planes)
        self.conv2 = tnn.Conv2d(planes, planes, 3, stride, 1, bias=False)
        self.bn2 = tnn.BatchNorm2d(planes)
        self.conv3 = tnn.Conv2d(planes, cout, 1, bias=False)
        self.bn3 = tnn.BatchNorm2d(cout)
        self.downsample = None
        if stride != 1 or cin != cout:
            self.downsample = tnn.Sequential(
                tnn.Conv2d(cin, cout, 1, stride, bias=False),
                tnn.BatchNorm2d(cout))

    def forward(self, x):
        idn = x if self.downsample is None else self.downsample(x)
        y = self.bn1(self.conv1(x)).relu()
        y = self.bn2(self.conv2(y)).relu()
        y = self.bn3(self.conv3(y))
        return (y + idn).relu()


class TorchMiniResNet50(tnn.Module):
    """Two bottleneck stages on the torchvision plan — exercises the
    converter's 3-conv path used by resnet50/101/152."""

    def __init__(self, width=8, num_classes=6):
        super().__init__()
        self.conv1 = tnn.Conv2d(3, width, 7, 2, 3, bias=False)
        self.bn1 = tnn.BatchNorm2d(width)
        self.maxpool = tnn.MaxPool2d(3, 2, 1)
        self.layer1 = tnn.Sequential(TorchBottleneck(width, width))
        self.layer2 = tnn.Sequential(
            TorchBottleneck(width * 4, width * 2, stride=2))
        self.fc = tnn.Linear(width * 8, num_classes)

    def forward(self, x):
        x = self.maxpool(self.bn1(self.conv1(x)).relu())
        x = self.layer2(self.layer1(x))
        return self.fc(x.mean(dim=(2, 3)))


class TorchGroupedBottleneck(tnn.Module):
    """torchvision bottleneck with cardinality: width =
    int(planes * base_width / 64) * groups, grouped 3x3 — the
    ResNeXt/Wide-ResNet block plan."""

    def __init__(self, cin, planes, stride=1, groups=4, base_width=32):
        super().__init__()
        cout = planes * 4
        width = int(planes * base_width / 64) * groups
        self.conv1 = tnn.Conv2d(cin, width, 1, bias=False)
        self.bn1 = tnn.BatchNorm2d(width)
        self.conv2 = tnn.Conv2d(width, width, 3, stride, 1,
                                groups=groups, bias=False)
        self.bn2 = tnn.BatchNorm2d(width)
        self.conv3 = tnn.Conv2d(width, cout, 1, bias=False)
        self.bn3 = tnn.BatchNorm2d(cout)
        self.downsample = None
        if stride != 1 or cin != cout:
            self.downsample = tnn.Sequential(
                tnn.Conv2d(cin, cout, 1, stride, bias=False),
                tnn.BatchNorm2d(cout))

    def forward(self, x):
        idn = x if self.downsample is None else self.downsample(x)
        y = self.bn1(self.conv1(x)).relu()
        y = self.bn2(self.conv2(y)).relu()
        y = self.bn3(self.conv3(y))
        return (y + idn).relu()


class TorchMiniResNeXt(tnn.Module):
    def __init__(self, width=8, num_classes=6):
        super().__init__()
        self.conv1 = tnn.Conv2d(3, width, 7, 2, 3, bias=False)
        self.bn1 = tnn.BatchNorm2d(width)
        self.maxpool = tnn.MaxPool2d(3, 2, 1)
        self.layer1 = tnn.Sequential(TorchGroupedBottleneck(width, width))
        self.layer2 = tnn.Sequential(
            TorchGroupedBottleneck(width * 4, width * 2, stride=2))
        self.fc = tnn.Linear(width * 8, num_classes)

    def forward(self, x):
        x = self.maxpool(self.bn1(self.conv1(x)).relu())
        x = self.layer2(self.layer1(x))
        return self.fc(x.mean(dim=(2, 3)))


def test_grouped_bottleneck_logits_match_torch():
    """Converter + forward parity on the grouped/widened bottleneck
    (resnext/wide_resnet family): torch's [out, in/groups, kh, kw]
    grouped kernel must land bit-compatibly in Flax's
    feature_group_count layout."""
    from imagent_tpu.models.resnet import Bottleneck, ResNet

    torch.manual_seed(11)
    tm = TorchMiniResNeXt().eval()
    with torch.no_grad():
        _randomize_bn_stats(tm)
    params, stats = resnet_from_torch(tm.state_dict(), (1, 1))
    fm = ResNet(stage_sizes=(1, 1), block_cls=Bottleneck, num_classes=6,
                num_filters=8, groups=4, base_width=32)

    x = np.random.default_rng(6).normal(
        size=(4, 3, 32, 32)).astype(np.float32)
    with torch.no_grad():
        want = tm(torch.from_numpy(x)).numpy()
    got = np.asarray(fm.apply(
        {"params": params, "batch_stats": stats},
        np.transpose(x, (0, 2, 3, 1)), train=False))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_bottleneck_logits_match_torch():
    """Converter parity on the Bottleneck (resnet50-family) block plan."""
    from imagent_tpu.models.resnet import Bottleneck, ResNet

    torch.manual_seed(9)
    tm = TorchMiniResNet50().eval()
    with torch.no_grad():
        _randomize_bn_stats(tm)
    params, stats = resnet_from_torch(tm.state_dict(), (1, 1))
    fm = ResNet(stage_sizes=(1, 1), block_cls=Bottleneck, num_classes=6,
                num_filters=8)

    x = np.random.default_rng(5).normal(
        size=(4, 3, 32, 32)).astype(np.float32)
    with torch.no_grad():
        want = tm(torch.from_numpy(x)).numpy()
    got = np.asarray(fm.apply(
        {"params": params, "batch_stats": stats},
        np.transpose(x, (0, 2, 3, 1)), train=False))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_resnet_to_torch_roundtrip():
    """Export is the exact inverse of import: torch -> ours -> torch is
    bit-identical, and the exported dict loads into a real torch model
    reproducing our logits — the train-here/serve-in-torch path."""
    from imagent_tpu.compat import resnet_to_torch
    from imagent_tpu.models import create_model

    torch.manual_seed(13)
    tm = TorchResNet18(num_classes=10).eval()
    with torch.no_grad():
        _randomize_bn_stats(tm)
    sd0 = {k: v.numpy() for k, v in tm.state_dict().items()}

    params, stats = resnet_from_torch(sd0, (2, 2, 2, 2))
    sd1 = resnet_to_torch(params, stats, (2, 2, 2, 2))
    for k, v in sd0.items():
        if k.endswith("num_batches_tracked"):
            continue
        np.testing.assert_array_equal(sd1[k], v, err_msg=k)

    # Load the export into a FRESH torch model; logits must match the
    # Flax forward on the same weights.
    tm2 = TorchResNet18(num_classes=10).eval()
    tm2.load_state_dict({k: torch.from_numpy(np.asarray(v).copy())
                         for k, v in sd1.items()
                         if not k.endswith("num_batches_tracked")},
                        strict=False)
    fm = create_model("resnet18", num_classes=10)
    x = np.random.default_rng(8).normal(
        size=(4, 3, 64, 64)).astype(np.float32)
    with torch.no_grad():
        want = tm2(torch.from_numpy(x)).numpy()
    got = np.asarray(fm.apply(
        {"params": params, "batch_stats": stats},
        np.transpose(x, (0, 2, 3, 1)), train=False))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_resnext_to_torch_roundtrip():
    """Grouped kernels survive the inverse transpose bit-exactly."""
    from imagent_tpu.compat import resnet_to_torch

    torch.manual_seed(17)
    tm = TorchMiniResNeXt().eval()
    with torch.no_grad():
        _randomize_bn_stats(tm)
    sd0 = {k: v.numpy() for k, v in tm.state_dict().items()}
    params, stats = resnet_from_torch(sd0, (1, 1))
    sd1 = resnet_to_torch(params, stats, (1, 1))
    for k, v in sd0.items():
        if k.endswith("num_batches_tracked"):
            continue
        np.testing.assert_array_equal(sd1[k], v, err_msg=k)


# --- ConvNeXt (models/convnext.py <-> torchvision naming) ---


class LayerNorm2d(tnn.Module):
    """torchvision's LayerNorm2d: LN over C of an NCHW tensor."""

    def __init__(self, dim):
        super().__init__()
        self.weight = tnn.Parameter(torch.ones(dim))
        self.bias = tnn.Parameter(torch.zeros(dim))

    def forward(self, x):
        x = x.permute(0, 2, 3, 1)
        x = torch.nn.functional.layer_norm(
            x, (x.shape[-1],), self.weight, self.bias, eps=1e-6)
        return x.permute(0, 3, 1, 2)


class _ToNHWC(tnn.Module):
    def forward(self, x):
        return x.permute(0, 2, 3, 1)


class _ToNCHW(tnn.Module):
    def forward(self, x):
        return x.permute(0, 3, 1, 2)


class TorchCNBlock(tnn.Module):
    """torchvision CNBlock: the Sequential indices (0 dwconv, 2 LN,
    3/5 Linears) and the ``layer_scale`` parameter name match the real
    state_dict layout the converter walks."""

    def __init__(self, dim):
        super().__init__()
        self.block = tnn.Sequential(
            tnn.Conv2d(dim, dim, 7, padding=3, groups=dim, bias=True),
            _ToNHWC(),
            tnn.LayerNorm(dim, eps=1e-6),
            tnn.Linear(dim, 4 * dim),
            tnn.GELU(),
            tnn.Linear(4 * dim, dim),
            _ToNCHW(),
        )
        self.layer_scale = tnn.Parameter(torch.full((dim, 1, 1), 1e-6))

    def forward(self, x):
        return x + self.layer_scale * self.block(x)


class TorchMiniConvNeXt(tnn.Module):
    """torchvision ConvNeXt plan at toy scale: features = [stem,
    stage, (LN+conv downsample, stage) x 3], avgpool, classifier =
    [LayerNorm2d, Flatten, Linear]."""

    def __init__(self, depths=(1, 1, 2, 1), dims=(8, 12, 16, 24),
                 num_classes=5):
        super().__init__()
        layers = [tnn.Sequential(tnn.Conv2d(3, dims[0], 4, 4),
                                 LayerNorm2d(dims[0]))]
        for i, (depth, dim) in enumerate(zip(depths, dims)):
            if i > 0:
                layers.append(tnn.Sequential(
                    LayerNorm2d(dims[i - 1]),
                    tnn.Conv2d(dims[i - 1], dim, 2, 2)))
            layers.append(tnn.Sequential(
                *[TorchCNBlock(dim) for _ in range(depth)]))
        self.features = tnn.Sequential(*layers)
        self.avgpool = tnn.AdaptiveAvgPool2d(1)
        self.classifier = tnn.Sequential(
            LayerNorm2d(dims[-1]), tnn.Flatten(1),
            tnn.Linear(dims[-1], num_classes))

    def forward(self, x):
        return self.classifier(self.avgpool(self.features(x)))


def test_convnext_logits_match_torch():
    """Converted torch ConvNeXt weights reproduce the torch forward in
    the Flax model (the ResNet/ViT parity standard)."""
    import jax
    import jax.numpy as jnp

    from imagent_tpu.compat import convnext_from_torch
    from imagent_tpu.models.convnext import ConvNeXt

    torch.manual_seed(3)
    tm = TorchMiniConvNeXt()
    with torch.no_grad():  # randomize so mapping bugs can't hide
        for p in tm.parameters():
            p.copy_(torch.randn_like(p) * 0.1)
    tm.eval()

    sd = {k: v.numpy() for k, v in tm.state_dict().items()}
    params = convnext_from_torch(sd)

    fm = ConvNeXt(depths=(1, 1, 2, 1), dims=(8, 12, 16, 24),
                  num_classes=5)
    x = np.random.default_rng(0).normal(
        size=(2, 32, 32, 3)).astype(np.float32)
    want = tm(torch.from_numpy(x).permute(0, 3, 1, 2)).detach().numpy()
    got = np.asarray(fm.apply({"params": params},
                              jnp.asarray(x), train=False))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    # The converted tree is structurally exact vs a fresh init.
    ref = fm.init(jax.random.key(0), jnp.asarray(x), train=False)
    assert (jax.tree_util.tree_structure(ref["params"])
            == jax.tree_util.tree_structure(
                jax.tree_util.tree_map(jnp.asarray, params)))


def test_convnext_to_torch_roundtrip():
    """Export inverts import bit-exactly, including the (dim,1,1)
    layer_scale shape torchvision expects."""
    from imagent_tpu.compat import convnext_from_torch, convnext_to_torch

    torch.manual_seed(4)
    tm = TorchMiniConvNeXt()
    sd = {k: v.detach().numpy() for k, v in tm.state_dict().items()}
    back = convnext_to_torch(convnext_from_torch(sd))
    assert set(back) == set(sd)
    for k in sd:
        np.testing.assert_array_equal(back[k], sd[k])
        assert back[k].shape == sd[k].shape


def test_vit_to_torch_rejects_stacked_params():
    """ADVICE r5 #1 regression: a stacked/pipelined ViT carries its
    encoder as ONE leading-axis-stacked `encoder` subtree (nn.scan) —
    no `encoder_layer_i` keys — and the old exporter silently wrote a
    state_dict with only stem/ln/head tensors. It must refuse before
    writing anything."""
    from imagent_tpu.compat import vit_to_torch

    m = VisionTransformer(patch_size=8, hidden_dim=32, num_layers=2,
                          num_heads=4, mlp_dim=64, num_classes=8,
                          stacked=True)
    v = m.init(jax.random.key(0),
               np.zeros((1, 16, 16, 3), np.float32), train=False)
    assert "encoder_layer_0" not in v["params"]  # the stacked layout
    with pytest.raises(ValueError,
                       match="stacked/pipelined params not supported"):
        vit_to_torch(v["params"])


def test_export_torch_prefers_best_checkpoint(tmp_path, capsys):
    """ADVICE r5 #2 regression: the run summary headlines best_top1
    and the reference saves its .pt at the best epoch — so the
    end-of-training --export-torch must ship the BEST checkpoint's
    weights when --save-model kept one, and fall back to the final
    state with a LOUD warning otherwise."""
    import jax.numpy as jnp

    from imagent_tpu import checkpoint as ckpt_lib
    from imagent_tpu.compat import to_torch_state_dict
    from imagent_tpu.config import Config
    from imagent_tpu.engine import _export_torch
    from imagent_tpu.train import create_train_state, make_optimizer

    model = create_model("resnet18", num_classes=4)
    final = create_train_state(model, jax.random.key(0), 16,
                               make_optimizer())
    # A BEST checkpoint with distinguishable weights (the +1.0 shift).
    best = final.replace(params=jax.tree.map(lambda p: p + 1.0,
                                             final.params))
    ckpt_lib.save(str(tmp_path / "ckpt"), ckpt_lib.BEST, best,
                  {"epoch": 2, "best_top1": 77.0})

    pt = tmp_path / "best.pt"
    cfg = Config(backend="cpu", arch="resnet18", num_classes=4, image_size=16,
                 save_model=True, export_torch=str(pt),
                 ckpt_dir=str(tmp_path / "ckpt"))
    _export_torch(cfg, final, is_master=True, prefer_best=True)
    assert "exporting the BEST checkpoint (epoch 3, top1 77.000)" in (
        capsys.readouterr().out)
    sd = torch.load(pt, weights_only=True)
    want = to_torch_state_dict("resnet18", jax.device_get(best.params),
                               jax.device_get(best.batch_stats))
    assert set(sd) == set(want)
    for k in want:
        np.testing.assert_allclose(sd[k].numpy(),
                                   np.asarray(want[k], np.float32),
                                   rtol=1e-6, atol=1e-6, err_msg=k)

    # No restorable BEST (--save-model off): final state + warning.
    pt2 = tmp_path / "final.pt"
    cfg2 = cfg.replace(save_model=False, export_torch=str(pt2),
                       ckpt_dir=str(tmp_path / "none"))
    _export_torch(cfg2, final, is_master=True, prefer_best=True)
    out = capsys.readouterr().out
    assert "WARNING: --export-torch exporting the FINAL-epoch" in out
    assert "--save-model is off" in out
    sd2 = torch.load(pt2, weights_only=True)
    want2 = to_torch_state_dict("resnet18", jax.device_get(final.params),
                                jax.device_get(final.batch_stats))
    for k in want2:
        np.testing.assert_allclose(sd2[k].numpy(),
                                   np.asarray(want2[k], np.float32),
                                   rtol=1e-6, atol=1e-6, err_msg=k)
