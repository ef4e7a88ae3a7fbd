"""Torch binding tests — parity with reference pytorch CI
(.github/workflows/pytorch.yaml: torch_simple_example.py + test_torch_ops.py
under np 1..4), here driven in-process over multi-engine thread clusters."""


import numpy as np
import pytest

torch = pytest.importorskip("torch")

from kungfu_tpu.comm.engine import CollectiveEngine
from kungfu_tpu.comm.host import HostChannel
from kungfu_tpu.plan import PeerID, PeerList, Strategy
from kungfu_tpu.torch.ops import clib, collective
from kungfu_tpu.torch.optimizers.sync_sgd import SynchronousSGDOptimizer

from tests._util import run_all

_port = [27000]


def make_engines(n):
    _port[0] += n + 2
    base = _port[0]
    peers = PeerList.of(*(PeerID("127.0.0.1", base + i) for i in range(n)))
    chans = [HostChannel(p, bind_host="127.0.0.1") for p in peers]
    engines = [CollectiveEngine(c, peers, Strategy.BINARY_TREE_STAR) for c in chans]
    return engines, chans




def close_all(engines, chans):
    for e in engines:
        e.close()
    for c in chans:
        c.close()


class TestClib:
    @pytest.mark.parametrize(
        "dtype",
        [torch.float16, torch.bfloat16, torch.float32, torch.float64,
         torch.int32, torch.int64, torch.uint8, torch.int8],
    )
    def test_roundtrip(self, dtype):
        t = torch.arange(12).reshape(3, 4).to(dtype)
        a = clib.to_numpy(t)
        back = clib.from_numpy(a, t)
        assert back.dtype == dtype
        assert torch.equal(back.reshape(t.shape), t)

    def test_unsupported(self):
        with pytest.raises(TypeError):
            clib.to_numpy(torch.zeros(2, dtype=torch.complex64))


class TestSingleProcess:
    def test_all_reduce_identity(self):
        t = torch.randn(5)
        out = collective.all_reduce(t, engine=None)
        assert torch.equal(out, t)

    def test_broadcast_parameters_noop(self):
        m = torch.nn.Linear(4, 2)
        before = {k: v.clone() for k, v in m.state_dict().items()}
        collective.broadcast_parameters(m.state_dict(), engine=None)
        for k, v in m.state_dict().items():
            assert torch.equal(v, before[k])

    def test_sync_sgd_matches_plain(self):
        torch.manual_seed(0)
        m1 = torch.nn.Linear(4, 2)
        m2 = torch.nn.Linear(4, 2)
        m2.load_state_dict(m1.state_dict())
        o1 = torch.optim.SGD(m1.parameters(), lr=0.1)
        o2 = SynchronousSGDOptimizer(torch.optim.SGD(m2.parameters(), lr=0.1))
        x = torch.randn(8, 4)
        for m, o in ((m1, o1), (m2, o2)):
            o.zero_grad()
            m(x).pow(2).sum().backward()
            o.step()
        for a, b in zip(m1.parameters(), m2.parameters()):
            assert torch.allclose(a, b)


class TestMultiEngine:
    def test_all_reduce_mean(self):
        engines, chans = make_engines(3)
        try:
            tensors = [torch.full((7,), float(i + 1)) for i in range(3)]
            outs = run_all(
                [lambda e=e, t=t: collective.all_reduce(t, op="mean", engine=e, name="t0")
                 for e, t in zip(engines, tensors)]
            )
            for o in outs:
                assert torch.allclose(o, torch.full((7,), 2.0))
        finally:
            close_all(engines, chans)

    def test_all_gather_stacks_ranks(self):
        engines, chans = make_engines(3)
        try:
            tensors = [torch.full((2, 2), float(i)) for i in range(3)]
            outs = run_all(
                [lambda e=e, t=t: collective.all_gather(t, engine=e,
                                                        name="ag0")
                 for e, t in zip(engines, tensors)]
            )
            for o in outs:
                assert o.shape == (3, 2, 2)
                for r in range(3):
                    assert torch.allclose(o[r], torch.full((2, 2),
                                                           float(r)))
        finally:
            close_all(engines, chans)

    def test_async_handles(self):
        engines, chans = make_engines(2)
        try:
            def worker(e, val):
                grads = [torch.full((4,), val), torch.full((3,), 2 * val)]
                handles = [
                    collective.all_reduce_async(g, op="mean", engine=e, name=f"g{i}")
                    for i, g in enumerate(grads)
                ]
                collective.wait_all_handles(handles)
                return grads

            outs = run_all([lambda e=e, v=float(r + 1): worker(e, v)
                            for r, e in enumerate(engines)])
            for grads in outs:
                assert torch.allclose(grads[0], torch.full((4,), 1.5))
                assert torch.allclose(grads[1], torch.full((3,), 3.0))
        finally:
            close_all(engines, chans)

    def test_async_three_ranks_many_grads(self):
        """Regression: a bounded shared thread pool deadlocked when
        ranks x grads exceeded the pool size (blocked waiters starved the
        rank they waited for)."""
        engines, chans = make_engines(3)
        try:
            def worker(e, val):
                grads = [torch.full((4,), val + i) for i in range(3)]
                handles = [
                    collective.all_reduce_async(g, op="sum", engine=e, name=f"m{i}")
                    for i, g in enumerate(grads)
                ]
                collective.wait_all_handles(handles)
                return grads

            outs = run_all(
                [lambda e=e, v=float(r) : worker(e, v) for r, e in enumerate(engines)],
                timeout=30,
            )
            for grads in outs:
                for i, g in enumerate(grads):
                    assert torch.allclose(g, torch.full((4,), 3.0 + 3 * i))
        finally:
            close_all(engines, chans)

    def test_int_mean_rejected(self):
        with pytest.raises(TypeError):
            collective.all_reduce(torch.ones(3, dtype=torch.int64), op="mean")

    def test_broadcast_parameters(self):
        engines, chans = make_engines(2)
        try:
            # models built BEFORE the worker threads start: torch's seed
            # is process-global, so seeding inside the racing workers made
            # rank 0's "seed-0" weights nondeterministic (flaky mismatch
            # against the ref model, with the broadcast itself correct)
            models = []
            for rank in range(2):
                torch.manual_seed(rank)
                models.append(torch.nn.Linear(3, 3))

            def worker(m, e):
                collective.broadcast_parameters(m.state_dict(), engine=e)
                return {k: v.clone() for k, v in m.state_dict().items()}

            outs = run_all([lambda m=m, e=e: worker(m, e)
                            for m, e in zip(models, engines)])
            torch.manual_seed(0)
            ref = torch.nn.Linear(3, 3).state_dict()
            for sd in outs:
                for k in ref:
                    assert torch.allclose(sd[k], ref[k])
        finally:
            close_all(engines, chans)

    def test_sync_sgd_converges_identically(self):
        """Both ranks end with identical weights == serial large-batch SGD."""
        engines, chans = make_engines(2)
        try:
            torch.manual_seed(7)
            X = torch.randn(16, 4)
            w_true = torch.tensor([[1.0], [-2.0], [0.5], [3.0]])
            Y = X @ w_true

            # same init on all ranks, built BEFORE the worker threads
            # start (torch's seed is process-global: see above)
            models = []
            for _ in engines:
                torch.manual_seed(1)
                models.append(torch.nn.Linear(4, 1, bias=False))

            def worker(rank, e):
                m = models[rank]
                opt = SynchronousSGDOptimizer(
                    torch.optim.SGD(m.parameters(), lr=0.05), engine=e
                )
                xs, ys = X[rank::2], Y[rank::2]
                for _ in range(30):
                    opt.zero_grad()
                    ((m(xs) - ys) ** 2).mean().backward()
                    opt.step()
                return m.weight.detach().clone()

            outs = run_all([lambda r=r, e=e: worker(r, e) for r, e in enumerate(engines)])
            assert torch.allclose(outs[0], outs[1], atol=1e-6)
        finally:
            close_all(engines, chans)
