"""PyTorch binding tests (reference test/test_torch.py), rank-aware —
run standalone (size 1) or under ``hvdrun -np N``."""

import numpy as np
import pytest

torch = pytest.importorskip("torch")


@pytest.fixture()
def thvd(hvd):
    import horovod_tpu.torch as thvd
    return thvd


def test_torch_allreduce(thvd, rank, size):
    x = torch.ones(4, 3) * (rank + 1)
    out = thvd.allreduce(x, op=thvd.Sum, name="tt.sum")
    assert torch.allclose(out, torch.full((4, 3),
                                          float(sum(range(1, size + 1)))))
    out = thvd.allreduce(x, name="tt.avg")
    assert torch.allclose(out, torch.full((4, 3), (size + 1) / 2))


def test_torch_allreduce_inplace(thvd, rank, size):
    x = torch.ones(5) * (rank + 1)
    thvd.allreduce_(x, op=thvd.Sum, name="tt.inplace")
    assert torch.allclose(x, torch.full((5,), float(sum(range(1, size + 1)))))


def test_torch_allreduce_adasum(thvd, rank, size):
    """op=Adasum reaches the native scaled-projection butterfly through
    the torch binding: identical tensors combine to themselves (the
    Adasum identity — a Sum or Average alias would return size*x or x
    trivially too, so also check the 2-rank a,3a case)."""
    x = torch.linspace(1.0, 2.0, 12)
    out = thvd.allreduce(x, op=thvd.Adasum, name="tt.adasum.ident")
    assert torch.allclose(out, x, rtol=1e-5)
    if size == 2:
        y = x * (1.0 if rank == 0 else 3.0)
        out = thvd.allreduce(y, op=thvd.Adasum, name="tt.adasum.par")
        # a, 3a -> (1-3/2)a + (1-1/6)3a = 2a
        assert torch.allclose(out, 2.0 * x, rtol=1e-4)


def test_torch_allreduce_fp16_compression(thvd, rank, size):
    x = torch.ones(8) * (rank + 1)
    out = thvd.allreduce(x, op=thvd.Sum, name="tt.fp16",
                         compression=thvd.Compression.fp16)
    assert out.dtype == torch.float32
    assert torch.allclose(out, torch.full((8,),
                                          float(sum(range(1, size + 1)))))


def test_torch_allgather(thvd, rank, size):
    x = torch.ones(rank + 1, 2) * rank
    out = thvd.allgather(x, name="tt.ag")
    assert out.shape == (size * (size + 1) // 2, 2)


def test_torch_broadcast(thvd, rank, size):
    x = torch.arange(6, dtype=torch.float32) * (rank + 1)
    out = thvd.broadcast(x, 0, name="tt.bc")
    assert torch.allclose(out, torch.arange(6, dtype=torch.float32))
    thvd.broadcast_(x, 0, name="tt.bc_")
    assert torch.allclose(x, torch.arange(6, dtype=torch.float32))


def test_distributed_optimizer_sgd(thvd, rank, size):
    """Gradients are averaged across ranks; parameters stay identical
    (reference test_torch.py optimizer tests)."""
    torch.manual_seed(0)
    model = torch.nn.Linear(4, 2)
    # Same initial weights everywhere (seed), rank-dependent data.
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    opt = thvd.DistributedOptimizer(
        opt, named_parameters=model.named_parameters())

    x = torch.ones(3, 4) * (rank + 1)
    y = model(x).sum()
    y.backward()
    opt.step()

    gathered = thvd.allgather(
        torch.cat([p.data.reshape(1, -1) for p in model.parameters()], 1),
        name="tt.opt.params")
    for r in range(size):
        assert torch.allclose(gathered[0], gathered[r], atol=1e-6), \
            f"rank {r} diverged"
    opt.zero_grad()


def test_distributed_optimizer_validation(thvd, rank, size):
    model = torch.nn.Linear(2, 2)
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    with pytest.raises(ValueError, match="unique"):
        thvd.DistributedOptimizer(
            opt, named_parameters=[("w", model.weight), ("w", model.bias)])
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    with pytest.raises(ValueError, match="tuples"):
        thvd.DistributedOptimizer(opt, named_parameters=[model.weight])
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    with pytest.raises(ValueError, match="does not cover all"):
        thvd.DistributedOptimizer(
            opt, named_parameters=[("w", model.weight)])


def test_zero_grad_race_guard(thvd, rank, size):
    """zero_grad between backward and step is prohibited (reference
    torch/__init__.py:197-202)."""
    if size < 2:
        pytest.skip("hooks only active multi-process")
    model = torch.nn.Linear(2, 1)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1),
        named_parameters=model.named_parameters())
    model(torch.ones(1, 2)).sum().backward()
    with pytest.raises(AssertionError, match="zero_grad"):
        opt.zero_grad()
    opt.step()   # drain handles so the session stays healthy


def test_broadcast_parameters_state_dict(thvd, rank, size):
    model = torch.nn.Linear(3, 3)
    with torch.no_grad():
        for p in model.parameters():
            p.fill_(float(rank))
    thvd.broadcast_parameters(model.state_dict(), root_rank=0)
    for p in model.parameters():
        assert torch.allclose(p.data, torch.zeros_like(p))


def test_broadcast_optimizer_state(thvd, rank, size):
    torch.manual_seed(rank)  # deliberately diverged
    model = torch.nn.Linear(3, 1)
    opt = torch.optim.SGD(model.parameters(), lr=0.05, momentum=0.9)
    if rank == 0:
        model(torch.ones(2, 3)).sum().backward()
        opt.step()
        opt.zero_grad()
    thvd.broadcast_optimizer_state(opt, root_rank=0)
    state = opt.state_dict()
    gathered = thvd.allgather_object(
        {k: v for k, v in state["param_groups"][0].items()
         if k != "params"})
    assert all(g == gathered[0] for g in gathered)


def test_broadcast_optimizer_state_resume(thvd, rank, size):
    """Checkpoint-resume shape: only the ROOT has optimizer state; workers
    must fill theirs locally (no collective) and then receive the root's.
    Regression: a wrapped optimizer's dummy fill step used to allreduce on
    the worker subset only and deadlock."""
    torch.manual_seed(3)
    model = torch.nn.Linear(3, 2)
    opt = torch.optim.Adam(model.parameters(), lr=0.01)
    opt = thvd.DistributedOptimizer(
        opt, named_parameters=model.named_parameters())
    if rank == 0:
        # simulate restored state: a purely local base-class step
        for p in model.parameters():
            p.grad = torch.full_like(p, 0.5)
        type(opt).__mro__[1].step(opt)
        for p in model.parameters():
            p.grad = None
    thvd.broadcast_optimizer_state(opt, root_rank=0)
    sd = opt.state_dict()
    assert sd["state"], "optimizer state missing after broadcast"
    # every rank carries the root's step counter
    steps = [int(v["step"]) for v in sd["state"].values()]
    gathered = thvd.allgather_object(steps, name="opt.steps")
    assert all(g == gathered[0] for g in gathered)


def test_torch_alltoall_uneven_splits(thvd, rank, size):
    """alltoall with splits returns (output, received_splits) as torch
    tensors (later-Horovod contract)."""
    import torch
    splits = torch.arange(1, size + 1, dtype=torch.int64)
    rows = int(splits.sum())
    x = torch.full((rows, 2), float(rank))
    out, received = thvd.alltoall(x, splits=splits, name="th.a2av")
    assert torch.equal(received, torch.full((size,), rank + 1,
                                            dtype=received.dtype))
    assert out.shape == ((rank + 1) * size, 2)
    assert not torch.isnan(out).any()
    assert (out[:rank + 1] == 0).all()  # block from rank 0


def test_duplicate_inflight_name_error(thvd, rank, size):
    """Two concurrently in-flight tensors with one name must fail loudly
    (reference test_torch.py:390 duplicate-name error)."""
    if size < 2:
        pytest.skip("needs >= 2 ranks")
    # Large payload so h1 is still in flight when h2 submits (the check
    # is local, at submit time).  Do NOT wait on h2: if the race ever
    # resolved differently on one rank, waiting would deadlock the suite
    # instead of failing the assertion.
    h1 = thvd.allreduce_async(torch.ones(1 << 21), name="tt.dup")
    with pytest.raises(Exception, match="same name"):
        thvd.allreduce_async(torch.ones(1 << 21), name="tt.dup")
    thvd.synchronize(h1)


def test_backward_passes_per_step(thvd, rank, size):
    """Gradient accumulation: the allreduce fires on the Nth backward
    (reference test_torch.py optimizer accumulation tests)."""
    if size < 2:
        pytest.skip("hooks only active multi-process")
    torch.manual_seed(0)
    model = torch.nn.Linear(3, 1)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.05),
        named_parameters=model.named_parameters(),
        backward_passes_per_step=2)
    for _ in range(2):   # two accumulation micro-batches
        model(torch.ones(2, 3) * (rank + 1)).sum().backward()
    opt.step()
    gathered = thvd.allgather(model.weight.data.reshape(1, -1),
                              name="tt.bpps.w")
    for r in range(size):
        assert torch.allclose(gathered[0], gathered[r], atol=1e-6)
    opt.zero_grad()


def test_gradient_clipping_interplay(thvd, rank, size):
    """synchronize -> clip -> step under skip_synchronize (reference
    test_torch.py:1266)."""
    if size < 2:
        pytest.skip("hooks only active multi-process")
    torch.manual_seed(0)
    model = torch.nn.Linear(3, 1)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1),
        named_parameters=model.named_parameters())
    (model(torch.ones(2, 3) * (rank + 1) * 100).sum()).backward()
    opt.synchronize()
    torch.nn.utils.clip_grad_norm_(model.parameters(), 1.0)
    total = torch.sqrt(sum((p.grad ** 2).sum()
                           for p in model.parameters()))
    assert total <= 1.0 + 1e-5
    with opt.skip_synchronize():
        opt.step()
    gathered = thvd.allgather(model.weight.data.reshape(1, -1),
                              name="tt.clip.w")
    for r in range(size):
        assert torch.allclose(gathered[0], gathered[r], atol=1e-6)
    opt.zero_grad()


def test_model_parallelism_disjoint_names(thvd, rank, size):
    """Different ranks may allreduce disjoint tensor sets under distinct
    names concurrently (reference test_torch.py:1158)."""
    if size < 2:
        pytest.skip("needs >= 2 ranks")
    # Every rank submits every name, but in rank-dependent ORDER — the
    # coordinator must tolerate unordered submission (the reference's
    # model-parallelism test is exactly this property).
    names = [f"tt.mp.{i}" for i in range(size)]
    order = names[rank:] + names[:rank]
    handles = [thvd.allreduce_async(torch.ones(8) * (rank + 1),
                                    name=n) for n in order]
    for h in handles:
        out = thvd.synchronize(h)
        assert torch.allclose(out, torch.full(
            (8,), (size + 1) / 2))


def test_dynamic_requires_grad(thvd, rank, size):
    """Freezing/unfreezing a param between steps must not deadlock
    (reference test_torch.py:1216): step() force-allreduces params whose
    hook did not fire."""
    if size < 2:
        pytest.skip("hooks only active multi-process")
    torch.manual_seed(0)
    model = torch.nn.Linear(3, 1)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.05),
        named_parameters=model.named_parameters())
    # Step 1: normal.
    model(torch.ones(2, 3) * (rank + 1)).sum().backward()
    opt.step()
    opt.zero_grad()
    # Step 2: freeze bias -> its hook never fires.  Give it a zero grad
    # on every rank so step()'s force-allreduce branch (the
    # deadlock-prevention behavior under test) actually has a tensor to
    # reduce — with grad None the branch is skipped entirely.
    model.bias.requires_grad_(False)
    model(torch.ones(2, 3) * (rank + 1)).sum().backward()
    model.bias.grad = torch.zeros_like(model.bias)
    opt.step()
    opt.zero_grad()
    model.bias.requires_grad_(True)
    gathered = thvd.allgather(model.weight.data.reshape(1, -1),
                              name="tt.dyn.w")
    for r in range(size):
        assert torch.allclose(gathered[0], gathered[r], atol=1e-6)


def test_skip_synchronize_requires_fresh_synchronize(thvd, rank, size):
    """A normal step() must consume the synchronized state: step ->
    backward -> skip_synchronize(step) without synchronize() raises
    instead of stepping on un-allreduced gradients."""
    if size < 2:
        pytest.skip("hooks only active multi-process")
    torch.manual_seed(0)
    model = torch.nn.Linear(2, 1)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1),
        named_parameters=model.named_parameters())
    model(torch.ones(1, 2)).sum().backward()
    opt.step()               # normal step (synchronizes internally)
    opt.zero_grad()
    model(torch.ones(1, 2)).sum().backward()
    with pytest.raises(AssertionError, match="synchronize"):
        with opt.skip_synchronize():
            opt.step()
    opt.synchronize()
    with opt.skip_synchronize():
        opt.step()           # now legal
    opt.zero_grad()


def test_grouped_allreduce_torch(thvd, rank, size):
    """grouped_allreduce: every tensor in flight together, one
    synchronize sweep; values average across ranks."""
    hvd = thvd
    ts = [torch.full((2, 3), float(rank + 1) * (i + 1)) for i in range(6)]
    outs = hvd.grouped_allreduce(ts, average=True, name="grp.torch")
    want = np.mean([r + 1 for r in range(size)])
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o.numpy(),
                                   np.full((2, 3), want * (i + 1),
                                           np.float32), rtol=1e-6)

    # async form: list handle -> synchronize returns the list
    hs = hvd.grouped_allreduce_async(ts, average=False, name="grp.torch2")
    outs = hvd.synchronize(hs)
    ssum = sum(r + 1 for r in range(size))
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o.numpy(),
                                   np.full((2, 3), ssum * (i + 1),
                                           np.float32), rtol=1e-6)
