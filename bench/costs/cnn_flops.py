"""Operations of a CNN's layers from their published shapes: two per
multiply-add of every convolution and fully connected layer, nothing for
BatchNorm, activations, pooling or additions (the convention of the
papers' own MAC counts). Read from the configuration's widths, not from
the program."""
from __future__ import annotations


def _conv(cin, cout, k, hw, groups=1):
    return 2 * (cin // groups) * cout * k * k * hw * hw


def resnet18_modules(config):
    """FLOPs per image of modules 0-5 (stem, four stages, classifier)."""
    size = int(config["input_size"])
    c0 = int(config["stem_width"])
    out = [_conv(3, c0, 7, size // 2)]
    hw, cin = size // 4, c0
    for si, cout in enumerate(int(c) for c in config["widths"]):
        f = 0
        for bi in range(int(config["blocks"][si])):
            stride = 2 if si > 0 and bi == 0 else 1
            if stride == 2:
                hw = (hw + 1) // 2
            f += _conv(cin, cout, 3, hw) + _conv(cout, cout, 3, hw)
            if stride != 1 or cin != cout:
                f += _conv(cin, cout, 1, hw)
            cin = cout
        out.append(f)
    out.append(2 * cin * int(config["num_classes"]))
    return out


MOBILENETV2_GROUPS = ((0,), (1,), (2,), (3, 4), (5, 6))


def mobilenetv2_modules(config):
    """FLOPs per image of modules 0-5 (module groups of the split points,
    then the 1x1 head conv and the classifier)."""
    hw = int(config["input_size"]) // 2
    cin = int(config["stem_width"])
    out = []
    for gi, group in enumerate(MOBILENETV2_GROUPS):
        f = _conv(3, cin, 3, hw) if gi == 0 else 0
        for si in group:
            t, c, n, s = (int(v) for v in config["stages"][si])
            for bi in range(n):
                stride = s if bi == 0 else 1
                mid = cin * t
                if t != 1:
                    f += _conv(cin, mid, 1, hw)
                if stride == 2:
                    hw = (hw + 1) // 2
                f += _conv(mid, mid, 3, hw, groups=mid)
                f += _conv(mid, c, 1, hw)
                cin = c
        out.append(f)
    head = int(config["head_width"])
    out.append(_conv(cin, head, 1, hw) + 2 * head * int(config["num_classes"]))
    return out


MODULES = {"resnet18": resnet18_modules, "mobilenetv2": mobilenetv2_modules}


def flops_per_image(config, start=0, stop=6):
    """FLOPs per image of modules [start, stop) of the configuration."""
    return sum(MODULES[config["arch"]](config)[start:stop])
