"""VGG (arXiv:1409.1556): a plan of 3x3 convolutions, each followed by
its ReLU, and 2x2 max pools ("M"), then a dense classifier.

``network`` keys: ``input_hw``, ``input_ch``, ``plan``, ``classifier``,
``n_classes``, ``bytes_per_elem``. Split layers are the feature modules
(convolution, ReLU, pool); the classifier runs on the server. The
device sends the raw image, or a module's output activation.
"""


def profile(net):
    hw, cin = net["input_hw"], net["input_ch"]
    macs, outs = [], []
    for p in net["plan"]:
        if p == "M":
            hw //= 2
            macs.append(hw * hw * cin)
            outs.append(hw * hw * cin)
        else:
            out = hw * hw * p
            macs += [9 * cin * p * hw * hw, out]      # 3x3 conv, then ReLU
            outs += [out, out]
            cin = p
    tail = 0.0
    a = hw * hw * cin
    for b in net["classifier"] + [net["n_classes"]]:
        tail += a * b
        a = b
    raw = net["input_hw"] ** 2 * net["input_ch"]
    return macs, [net["bytes_per_elem"] * n for n in [raw] + outs], tail
