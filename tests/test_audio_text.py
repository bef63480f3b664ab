"""paddle.audio + paddle.text parity tests."""
import os

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu import audio, text


RNG = np.random.RandomState(21)


class TestAudioFunctional:
    def test_mel_hz_roundtrip(self):
        f = np.array([100.0, 440.0, 4000.0, 10000.0])
        mel = audio.functional.hz_to_mel(f.tolist())
        back = audio.functional.mel_to_hz(mel)
        np.testing.assert_allclose(back, f, rtol=1e-5)

    def test_mel_hz_htk(self):
        # htk formula closed form
        np.testing.assert_allclose(audio.functional.hz_to_mel(700.0, htk=True),
                                   2595.0 * np.log10(2.0), rtol=1e-6)

    def test_fbank_shape_and_partition(self):
        fb = np.asarray(audio.functional.compute_fbank_matrix(16000, 512, n_mels=40)._value)
        assert fb.shape == (40, 257)
        assert (fb >= 0).all()
        # every filter has some mass
        assert (fb.sum(1) > 0).all()

    def test_window_types(self):
        for w in ["hann", "hamming", "blackman", "bartlett", "rectangular"]:
            arr = np.asarray(audio.functional.get_window(w, 64)._value)
            assert arr.shape == (64,)
            assert arr.max() <= 1.0 + 1e-6
        g = np.asarray(audio.functional.get_window(("gaussian", 7.0), 32)._value)
        assert g.argmax() in (15, 16)

    def test_power_to_db(self):
        s = P.to_tensor(np.array([1.0, 0.1, 0.01], np.float32))
        db = np.asarray(audio.functional.power_to_db(s, top_db=None)._value)
        np.testing.assert_allclose(db, [0.0, -10.0, -20.0], atol=1e-4)

    def test_dct_orthonormal(self):
        d = np.asarray(audio.functional.create_dct(13, 40)._value)
        assert d.shape == (40, 13)
        np.testing.assert_allclose(d.T @ d, np.eye(13), atol=1e-5)


class TestAudioFeatures:
    def test_spectrogram_parseval_vs_numpy(self):
        x = RNG.randn(1, 2048).astype(np.float32)
        spec = audio.features.Spectrogram(n_fft=256, hop_length=128, window="hann",
                                          power=2.0, center=False)
        out = np.asarray(spec(P.to_tensor(x))._value)
        assert out.shape[1] == 129  # bins
        # frame 0 against numpy stft
        w = 0.5 - 0.5 * np.cos(2 * np.pi * np.arange(256) / 256)
        ref = np.abs(np.fft.rfft(x[0, :256] * w)) ** 2
        np.testing.assert_allclose(out[0, :, 0], ref, rtol=1e-3, atol=1e-3)

    def test_melspectrogram_and_mfcc_shapes(self):
        x = P.to_tensor(RNG.randn(2, 4000).astype(np.float32))
        mel = audio.features.MelSpectrogram(sr=16000, n_fft=512, n_mels=40)
        m = mel(x)
        assert list(m.shape)[:2] == [2, 40]
        mfcc = audio.features.MFCC(sr=16000, n_mfcc=13, n_fft=512, n_mels=40)
        c = mfcc(x)
        assert list(c.shape)[:2] == [2, 13]

    def test_gradient_flows_to_waveform(self):
        x = P.to_tensor(RNG.randn(1, 1024).astype(np.float32))
        x.stop_gradient = False
        lm = audio.features.LogMelSpectrogram(sr=8000, n_fft=256, n_mels=20)
        P.sum(lm(x)).backward()
        assert x.grad is not None
        assert np.isfinite(np.asarray(x.grad._value)).all()


class TestAudioBackend:
    def test_wav_roundtrip(self, tmp_path):
        path = os.path.join(str(tmp_path), "t.wav")
        sig = (0.5 * np.sin(2 * np.pi * 440 * np.arange(8000) / 8000)).astype(np.float32)
        audio.save(path, P.to_tensor(sig[None, :]), 8000)
        back, sr = audio.load(path)
        assert sr == 8000
        np.testing.assert_allclose(np.asarray(back._value)[0], sig, atol=1e-3)


class TestViterbi:
    def _brute(self, pot, trans, include=False):
        T, N = pot.shape
        best, arg = -1e30, None
        import itertools

        for path in itertools.product(range(N), repeat=T):
            s = pot[0, path[0]] + (trans[N - 2, path[0]] if include else 0)
            for t in range(1, T):
                s += trans[path[t - 1], path[t]] + pot[t, path[t]]
            if include:
                s += trans[path[-1], N - 1]
            if s > best:
                best, arg = s, path
        return best, list(arg)

    def test_matches_brute_force(self):
        pot = RNG.randn(1, 4, 3).astype(np.float32)
        trans = RNG.randn(3, 3).astype(np.float32)
        scores, paths = text.viterbi_decode(P.to_tensor(pot), P.to_tensor(trans),
                                            P.to_tensor(np.array([4])),
                                            include_bos_eos_tag=False)
        ref_s, ref_p = self._brute(pot[0], trans, include=False)
        np.testing.assert_allclose(float(np.asarray(scores._value)[0]), ref_s, rtol=1e-5)
        assert np.asarray(paths._value)[0].tolist() == ref_p

    def test_bos_eos_mode(self):
        pot = RNG.randn(1, 3, 5).astype(np.float32)
        trans = RNG.randn(5, 5).astype(np.float32)
        scores, paths = text.viterbi_decode(P.to_tensor(pot), P.to_tensor(trans),
                                            P.to_tensor(np.array([3])),
                                            include_bos_eos_tag=True)
        ref_s, ref_p = self._brute(pot[0], trans, include=True)
        np.testing.assert_allclose(float(np.asarray(scores._value)[0]), ref_s, rtol=1e-5)
        assert np.asarray(paths._value)[0].tolist() == ref_p

    def test_batch_with_lengths(self):
        pot = RNG.randn(2, 5, 3).astype(np.float32)
        trans = RNG.randn(3, 3).astype(np.float32)
        scores, paths = text.viterbi_decode(P.to_tensor(pot), P.to_tensor(trans),
                                            P.to_tensor(np.array([5, 3])),
                                            include_bos_eos_tag=False)
        # batch element 1 decoded over its first 3 steps only
        s1, p1 = self._brute(pot[1, :3], trans, include=False)
        np.testing.assert_allclose(float(np.asarray(scores._value)[1]), s1, rtol=1e-4)
        assert np.asarray(paths._value)[1, :3].tolist() == p1

    def test_decoder_layer(self):
        trans = RNG.randn(4, 4).astype(np.float32)
        dec = text.ViterbiDecoder(P.to_tensor(trans), include_bos_eos_tag=False)
        pot = P.to_tensor(RNG.randn(2, 6, 4).astype(np.float32))
        scores, paths = dec(pot, P.to_tensor(np.array([6, 6])))
        assert list(paths.shape) == [2, 6]


class TestTextDatasets:
    def test_uci_housing_local(self, tmp_path):
        f = os.path.join(str(tmp_path), "housing.data")
        np.savetxt(f, RNG.rand(50, 14))
        ds = text.UCIHousing(data_file=f, mode="train")
        assert len(ds) == 40
        x, y = ds[0]
        assert x.shape == (13,) and y.shape == (1,)

    def test_missing_data_raises(self):
        with pytest.raises(RuntimeError, match="no network"):
            text.UCIHousing()
        with pytest.raises(RuntimeError, match="no network"):
            audio.datasets.ESC50(data_dir=None)


class TestHapiCallbacks:
    def test_early_stopping_and_history(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.hapi import Model
        from paddle_tpu.hapi.callbacks import EarlyStopping

        class DS:
            def __len__(self):
                return 16

            def __getitem__(self, i):
                x = np.zeros(4, np.float32)
                return x, np.zeros(1, np.float32)

        net = nn.Linear(4, 1)
        m = Model(net)
        m.prepare(P.optimizer.SGD(parameters=net.parameters(), learning_rate=0.0),
                  loss=lambda o, y: P.mean((o - y) ** 2))
        es = EarlyStopping(monitor="loss", patience=1, min_delta=1e-9)
        hist = m.fit(DS(), batch_size=8, epochs=10, verbose=0, callbacks=[es])
        # zero LR -> loss never improves -> stops after ~2-3 epochs, not 10
        assert len(hist["loss"]) < 10

    def test_lr_scheduler_callback_steps(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.hapi import Model
        from paddle_tpu.hapi.callbacks import LRScheduler as LRCb

        class DS:
            def __len__(self):
                return 8

            def __getitem__(self, i):
                return np.zeros(4, np.float32), np.zeros(1, np.float32)

        net = nn.Linear(4, 1)
        sched = P.optimizer.lr.StepDecay(learning_rate=0.1, step_size=2, gamma=0.5)
        opt = P.optimizer.SGD(parameters=net.parameters(), learning_rate=sched)
        m = Model(net)
        m.prepare(opt, loss=lambda o, y: P.mean((o - y) ** 2))
        m.fit(DS(), batch_size=4, epochs=1, verbose=0, callbacks=[LRCb(by_step=True)])
        assert sched.last_lr < 0.1  # stepped twice -> decayed at step 4


class TestASP:
    def test_prune_model_2of4(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.incubate import asp

        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        asp.prune_model(net)
        w = np.asarray(net[0].weight._value)
        assert abs(asp.calculate_density(net[0].weight) - 0.5) < 1e-6
        # every group of 4 along rows has exactly 2 nonzeros
        groups = w.reshape(-1)[: (w.size // 4) * 4].reshape(-1, 4)
        assert ((groups != 0).sum(1) == 2).all()

    def test_decorated_optimizer_keeps_sparsity(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.incubate import asp

        net = nn.Linear(8, 8)
        masks = asp.prune_model(net)
        assert masks
        opt = asp.decorate(P.optimizer.SGD(parameters=net.parameters(), learning_rate=0.1))
        x = P.to_tensor(np.random.randn(4, 8).astype(np.float32))
        for _ in range(3):
            loss = P.mean(net(x) ** 2)
            loss.backward()
            opt.step()
            opt.clear_grad()
        assert abs(asp.calculate_density(net.weight) - 0.5) < 1e-6

    def test_excluded_layers(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.incubate import asp

        net = nn.Sequential(nn.Linear(8, 8), nn.Linear(8, 8))
        asp.set_excluded_layers(net, ["0"])
        asp.prune_model(net)
        asp.reset_excluded_layers(net)
        assert asp.calculate_density(net[0].weight) == 1.0
        assert abs(asp.calculate_density(net[1].weight) - 0.5) < 1e-6

    def test_asp_custom_nm(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.incubate import asp

        net = nn.Linear(8, 8)
        asp.prune_model(net, n=1, m=4)
        assert abs(asp.calculate_density(net.weight) - 0.25) < 1e-6
