"""The XOR + byte-plane split transform's share of its HBM roofline.

Bytes are what the algorithm must move for the tensors that the window's
acknowledged uploads changed from their base, as the generator made them:
read the base and the fine-tune, write the planes, 3 bytes per byte of
tensor (6 B/elem for bf16, 12 for f32), from logical sizes and not padded
shapes. Time is the device time of the compiled transform programs named
in PROGRAMS: the Pallas kernel with the padding and relayout it needs,
since the kernel's own event reads operands those ops already staged on
chip."""

from bench.readers import changed_bytes, roofline

PROGRAMS = [r"bitx_encode_planes"]


def read(run):
    return roofline(run, PROGRAMS, 3.0 * changed_bytes(run))
