"""ResNet with bottleneck blocks (arXiv:1512.03385): a 7x7/2 stem and a
3x3/2 max pool, stages of (width, blocks) whose first block downsamples
(after the first stage) and projects, a global pool, then the
classifier.

``network`` keys: ``input_hw``, ``input_ch``, ``stages``,
``n_classes``, ``bytes_per_elem``. Split layers are the stem, the pool,
each block and the global pool; the classifier runs on the server. The
device sends the raw image, or a layer's output activation.
"""


def profile(net):
    hw = net["input_hw"] // 2
    macs, outs = [49 * 3 * 64 * hw * hw], [hw * hw * 64]   # 7x7/2 stem
    hw //= 2
    macs.append(hw * hw * 64)                               # 3x3/2 max pool
    outs.append(hw * hw * 64)
    cin = 64
    for s, (width, n) in enumerate(net["stages"]):
        for b in range(n):
            stride = 2 if (b == 0 and s > 0) else 1
            cout, ho = 4 * width, hw // stride
            m = (cin * width * hw * hw + 9 * width * width * ho * ho
                 + width * cout * ho * ho)
            if b == 0:
                m += cin * cout * ho * ho                   # projection
            macs.append(m)
            outs.append(ho * ho * cout)
            hw, cin = ho, cout
    macs.append(hw * hw * cin)                              # global pool
    outs.append(cin)
    raw = net["input_hw"] ** 2 * net["input_ch"]
    return (macs, [net["bytes_per_elem"] * n for n in [raw] + outs],
            cin * net["n_classes"])
