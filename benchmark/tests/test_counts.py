"""Operation and byte counts against hand-worked values."""

from benchmark.counts import cifar_resnet56, femnist_cnn


def test_cnn_forward_flops_by_hand():
    # conv1 784*25*1*32, conv2 196*25*32*64, fc1 3136*512, fc2 512*62
    macs = 627_200 + 10_035_200 + 1_605_632 + 31_744
    assert femnist_cnn.forward_flops_per_sample() == 2 * macs == 24_599_552


def test_resnet56_forward_flops_by_hand():
    stem = 1024 * 27 * 16
    s1 = 18 * 1024 * 144 * 16
    s2 = 256 * 144 * 32 + 17 * 256 * 288 * 32 + 256 * 16 * 32
    s3 = 64 * 288 * 64 + 17 * 64 * 576 * 64 + 64 * 32 * 64
    macs = stem + s1 + s2 + s3 + 640
    assert cifar_resnet56.forward_flops_per_sample() == 2 * macs
    assert len(cifar_resnet56.LAYERS) == 1 + 54 + 2 + 1


def test_cnn_step_ops_by_hand():
    ops = {n: (f, b) for n, f, b in femnist_cnn.matmul_ops_per_step(20)}
    # 4 layers forward and weight gradient, 3 with an input gradient
    assert len(ops) == 11 and "conv1.dx" not in ops
    f, b = ops["fc1.fwd"]
    assert f == 2 * 20 * 3136 * 512
    assert b == 4 * (20 * 3136 + 3136 * 512 + 20 * 512)
    # a training step is three times the forward, less conv1's input gradient
    total = sum(f for f, _ in ops.values())
    fwd = 20 * femnist_cnn.forward_flops_per_sample()
    assert total == 3 * fwd - 2 * 20 * 627_200


def test_resnet_step_ops_count():
    ops = cifar_resnet56.matmul_ops_per_step(64)
    assert len(ops) == 3 * 58 - 1
    total = sum(f for _, f, _ in ops)
    fwd = 64 * cifar_resnet56.forward_flops_per_sample()
    assert total == 3 * fwd - 2 * 64 * 1024 * 27 * 16
