"""Real image pixels through the real on-disk formats (round-5
review missing #1 / next-round #2).

- CIFAR-10 binary batches: native C++ decode (dl4j_read_cifar_bin) vs
  the numpy parser, on a bundled file of REAL photograph patches in the
  exact cifar-10-batches-bin row layout.
- LFW image-directory trees: the bundled REAL LFW subset (the same 4
  photos/2 people the reference ships in dl4j-test-resources/lfwtest)
  through the PIL reader, and through the native netpbm reader
  (dl4j_read_image_dir) after a netpbm conversion.
- A CNN accuracy gate on real pixels end-to-end.
"""

import os

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.fetchers import (
    CIFAR_SHAPE,
    load_cifar,
    load_lfw,
)
from deeplearning4j_tpu.datasets.fixtures import (
    lfw_fixture_dir,
    real_patches_cifar,
)
from deeplearning4j_tpu.native_rt import (
    native_available,
    read_cifar_bin,
    read_image_dir,
)

FIXTURES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "deeplearning4j_tpu", "datasets", "fixtures")
PATCHES_BIN = os.path.join(FIXTURES, "real_patches_batch.bin")


class TestCifarBinary:
    def test_fixture_decodes(self):
        imgs, labels = read_cifar_bin(PATCHES_BIN)
        assert imgs.shape == (200, *CIFAR_SHAPE)
        assert imgs.dtype == np.uint8 and labels.dtype == np.uint8
        assert set(np.unique(labels)) == {0, 1}
        # real photographs: rich value histogram, not a flat ramp
        assert len(np.unique(imgs)) > 200

    @pytest.mark.skipif(not native_available(), reason="no native lib")
    def test_native_matches_numpy_fallback(self, monkeypatch):
        """Cross-checks the two REAL code paths: native decode vs the
        numpy fallback branch of read_cifar_bin itself (the singleton
        cache is bypassed by patching NativeLib.load)."""
        from deeplearning4j_tpu.native_rt import lib as native_lib

        n_imgs, n_labels = read_cifar_bin(PATCHES_BIN)
        monkeypatch.setattr(
            native_lib.NativeLib, "load", classmethod(lambda cls: None))
        f_imgs, f_labels = native_lib.read_cifar_bin(PATCHES_BIN)
        np.testing.assert_array_equal(n_labels, f_labels)
        np.testing.assert_array_equal(n_imgs, f_imgs)

    def test_rejects_non_cifar_file(self, tmp_path):
        p = tmp_path / "bad.bin"
        p.write_bytes(b"\x00" * 100)  # not a multiple of 3073
        with pytest.raises(ValueError, match="not a CIFAR-10"):
            read_cifar_bin(str(p))

    def test_load_cifar_reads_real_batches(self, tmp_path, monkeypatch):
        """$DL4J_TPU_DATA_DIR/cifar-10-batches-bin with all 6 files ->
        the real parser runs (no synthetic substitution)."""
        root = tmp_path / "cifar-10-batches-bin"
        root.mkdir()
        raw = np.fromfile(PATCHES_BIN, dtype=np.uint8).reshape(-1, 3073)
        for i in range(1, 6):
            raw[(i - 1) * 20:i * 20].tofile(root / f"data_batch_{i}.bin")
        raw[100:120].tofile(root / "test_batch.bin")
        monkeypatch.setenv("DL4J_TPU_DATA_DIR", str(tmp_path))
        imgs, labels = load_cifar(train=True)
        assert imgs.shape == (100, *CIFAR_SHAPE)
        np.testing.assert_array_equal(labels, raw[:100, 0])
        timgs, _ = load_cifar(train=False)
        assert timgs.shape == (20, *CIFAR_SHAPE)

    def test_load_cifar_partial_dir_refuses(self, tmp_path, monkeypatch):
        root = tmp_path / "cifar-10-batches-bin"
        root.mkdir()
        (root / "data_batch_1.bin").write_bytes(b"\x00" * 3073)
        monkeypatch.setenv("DL4J_TPU_DATA_DIR", str(tmp_path))
        with pytest.raises(FileNotFoundError, match="missing"):
            load_cifar(train=True)


class TestLfwTree:
    def test_bundled_real_subset_via_pil(self):
        imgs, labels, names = load_lfw(
            num_people=2, image_shape=(3, 40, 40),
            root=lfw_fixture_dir())
        assert names == ["Zico", "Ziwang_Xu"]
        assert imgs.shape == (4, 3, 40, 40)
        np.testing.assert_array_equal(labels, [0, 0, 0, 1])
        # real photos: each image has a broad intensity spread
        assert all(int(im.max()) - int(im.min()) > 100 for im in imgs)

    @pytest.mark.skipif(not native_available(), reason="no native lib")
    def test_native_netpbm_tree_matches_pil(self, tmp_path):
        from PIL import Image

        root = tmp_path / "lfw"
        expected = {}
        for person in sorted(os.listdir(lfw_fixture_dir())):
            (root / person).mkdir(parents=True)
            src = os.path.join(lfw_fixture_dir(), person)
            for fn in sorted(os.listdir(src)):
                img = Image.open(os.path.join(src, fn)).convert("RGB")
                img.save(root / person / (fn[:-4] + ".ppm"))
                expected[person + "/" + fn] = np.asarray(
                    img, np.uint8).transpose(2, 0, 1)
        out = read_image_dir(str(root))
        assert out is not None
        imgs, labels = out
        exp = np.stack([expected[k] for k in sorted(expected)])
        np.testing.assert_array_equal(imgs, exp)
        np.testing.assert_array_equal(labels, [0, 0, 0, 1])

        # and load_lfw engages the native reader on netpbm trees,
        # resizing to the requested shape
        rimgs, rlabels, rnames = load_lfw(
            num_people=2, image_shape=(1, 28, 28), root=str(root))
        assert rimgs.shape == (4, 1, 28, 28)
        assert rnames == ["Zico", "Ziwang_Xu"]

    @pytest.mark.skipif(not native_available(), reason="no native lib")
    def test_native_rejects_mixed_shapes(self, tmp_path):
        from PIL import Image

        root = tmp_path / "tree"
        (root / "a").mkdir(parents=True)
        Image.new("RGB", (8, 8)).save(root / "a" / "x.ppm")
        Image.new("RGB", (9, 9)).save(root / "a" / "y.ppm")
        assert read_image_dir(str(root)) is None

    @pytest.mark.skipif(not native_available(), reason="no native lib")
    def test_native_defers_mixed_format_tree_to_pil(self, tmp_path):
        """A tree holding BOTH netpbm and jpg images must not be
        partially read natively (that would silently drop the jpgs) —
        the native reader refuses and load_lfw reads everything via
        PIL."""
        from PIL import Image

        root = tmp_path / "tree"
        (root / "a").mkdir(parents=True)
        Image.new("RGB", (8, 8), (200, 10, 10)).save(root / "a" / "x.ppm")
        Image.new("RGB", (8, 8), (10, 200, 10)).save(root / "a" / "y.jpg")
        assert read_image_dir(str(root)) is None
        imgs, labels, names = load_lfw(
            num_people=1, image_shape=(3, 8, 8), root=str(root))
        assert imgs.shape == (2, 3, 8, 8)  # BOTH images, via PIL

    @pytest.mark.skipif(not native_available(), reason="no native lib")
    def test_native_and_pil_paths_agree(self, tmp_path, monkeypatch):
        """Same netpbm tree, same requested shape: the native path and
        the PIL fallback must return identical pixels and labels."""
        from PIL import Image

        from deeplearning4j_tpu.native_rt import lib as native_lib

        root = tmp_path / "lfw"
        for person in sorted(os.listdir(lfw_fixture_dir())):
            (root / person).mkdir(parents=True)
            src = os.path.join(lfw_fixture_dir(), person)
            for fn in sorted(os.listdir(src)):
                Image.open(os.path.join(src, fn)).convert("RGB").save(
                    root / person / (fn[:-4] + ".ppm"))
        shape = (1, 28, 28)
        n_imgs, n_labels, n_names = load_lfw(
            num_people=2, image_shape=shape, root=str(root))
        monkeypatch.setattr(
            native_lib.NativeLib, "load", classmethod(lambda cls: None))
        p_imgs, p_labels, p_names = load_lfw(
            num_people=2, image_shape=shape, root=str(root))
        assert n_names == p_names
        np.testing.assert_array_equal(n_labels, p_labels)
        np.testing.assert_array_equal(n_imgs, p_imgs)

    @pytest.mark.skipif(not native_available(), reason="no native lib")
    def test_native_rejects_sub255_maxval(self, tmp_path):
        """Legal netpbm maxval < 255 would decode darker than PIL
        without rescaling — the native reader defers such files."""
        root = tmp_path / "tree"
        (root / "a").mkdir(parents=True)
        (root / "a" / "x.pgm").write_bytes(b"P5\n4 4\n15\n" + b"\x0f" * 16)
        assert read_image_dir(str(root)) is None


class TestRealPixelCnnGate:
    def test_cnn_learns_real_patches(self):
        """End-to-end: real photograph pixels, CIFAR binary format,
        native decode, CNN train -> held-out accuracy gate."""
        from deeplearning4j_tpu.nn.conf import (
            NeuralNetConfiguration,
            Updater,
        )
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.ops.losses import LossFunction

        tr, te = real_patches_cifar(n_test=40, seed=0)
        conf = (
            NeuralNetConfiguration.Builder()
            .seed(7)
            .learning_rate(3e-3)
            .updater(Updater.ADAM)
            .list()
            .layer(0, L.ConvolutionLayer(
                n_in=3, n_out=16, kernel_size=(3, 3), stride=(1, 1),
                activation="relu"))
            .layer(1, L.SubsamplingLayer(kernel_size=(2, 2),
                                         stride=(2, 2)))
            .layer(2, L.ConvolutionLayer(
                n_in=16, n_out=32, kernel_size=(3, 3), stride=(1, 1),
                activation="relu"))
            .layer(3, L.SubsamplingLayer(kernel_size=(2, 2),
                                         stride=(2, 2)))
            .layer(4, L.OutputLayer(
                n_out=2, activation="softmax",
                loss_function=LossFunction.MCXENT))
            .set_input_type(InputType.convolutional(32, 32, 3))
            .build()
        )
        net = MultiLayerNetwork(conf).init()
        for _ in range(30):
            net.fit(tr)
        ev = net.evaluate([te])
        assert ev.accuracy() >= 0.9, ev.stats()
