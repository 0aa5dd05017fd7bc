"""Today's readings of the dense decoder family, pinned: the weights after
the maps into and out of the program's tree, the reference's token gaps
and first two AdamW steps at the CPU smoke widths of both configurations,
the model FLOPs at the smoke and the real sizes, and each kernel's cost at
operand shapes the chip sends (padded ones among them). Recorded before the
family, kernel and driver lookups existed, read here through them: the
move changed no arithmetic.

The integers and FLOP counts are exact. A float32 reading is held to a
millionth of itself: any change of formula, layout or seed moves it by far
more, while XLA's CPU code may round the last bit otherwise on another
processor."""

from __future__ import annotations

import numpy as np
import pytest

from bench import reference, spec, weights
from bench.tests import tiny
from bench.traffic.batches import Batches

SEED = 2**33 + 5
OPTIMIZER = {"lr": 0.001, "b1": 0.9, "b2": 0.95, "eps": 1e-08, "weight_decay": 0.1,
             "grad_clip": 1.0}
CELLS = {"minicpm-2b": "minicpm-2b.pretrain",
         "starcoder2-15b": "starcoder2-15b.code-complete-unrolled"}

PINNED = {
    "minicpm-2b": {
        "leaves": {
            "attn_norm.scale": [129.42578125, 132.00892639160156],
            "embed": [1.2468719482421875, 6.5667942568852595],
            "final_norm.scale": [64.515625, 65.734375],
            "mlp_norm.scale": [128.30078125, 129.91026306152344],
            "w_down": [-13.450237274169922, 127.24823986653064],
            "w_gate": [-53.99171447753906, 253.60659662331454],
            "w_up": [-31.209747314453125, 257.20366079127416],
            "wk": [-9.71484375, 127.61939996900037],
            "wo": [-2.06365966796875, 129.70625742245466],
            "wq": [-7.9845428466796875, 125.16856945701875],
            "wv": [-3.1291656494140625, 126.58586797281168],
        },
        "token_gaps": [
            137.79780426621437, 3319.3961033821106, 5.043363094329834, 0.660934928804636,
            17.124493533745408, 0.24658899009227753],
        "losses": [5.512359142303467, 5.557163238525391],
        "grad_norms": {
            "attn_norm.scale": [0.01933574490249157, 0.0077115390449762344],
            "embed": [0.9715712666511536],
            "final_norm.scale": [0.007475648541003466],
            "mlp_norm.scale": [0.011601552367210388, 0.005680290050804615],
            "w_down": [0.08555343747138977, 0.04140328988432884],
            "w_gate": [0.06259655207395554, 0.030309533700346947],
            "w_up": [0.0653463676571846, 0.028701558709144592],
            "wk": [0.06818334013223648, 0.020398566499352455],
            "wo": [0.10496176034212112, 0.047081589698791504],
            "wq": [0.0724375769495964, 0.018453748896718025],
            "wv": [0.10072857141494751, 0.04895078390836716],
        },
        "change_norms": {
            "attn_norm.scale": [0.0, 0.0],
            "embed": [0.19047223031520844],
            "final_norm.scale": [0.0],
            "mlp_norm.scale": [0.0, 0.0],
            "w_down": [0.13985353708267212, 0.1399005651473999],
            "w_gate": [0.14086630940437317, 0.1401059478521347],
            "w_up": [0.14190495014190674, 0.1413613259792328],
            "wk": [0.09858600795269012, 0.10045386105775833],
            "wo": [0.09917932748794556, 0.09908514469861984],
            "wq": [0.09952997416257858, 0.09925420582294464],
            "wv": [0.09962958097457886, 0.09835582226514816],
        },
        "smoke_flops": [4872192.0, 1903104.0, 638976.0],
    },
    "starcoder2-15b": {
        "leaves": {
            "attn_norm.bias": [1.0432891845703125, 1.1852794534061104],
            "attn_norm.scale": [128.30078125, 129.91026306152344],
            "embed": [1.2468719482421875, 6.5667942568852595],
            "final_norm.bias": [-0.2985382080078125, 0.6010350782889873],
            "final_norm.scale": [63.90625, 64.38912963867188],
            "head": [-31.820205688476562, 256.01812901790254],
            "mlp_norm.bias": [-1.4261322021484375, 1.187176717678085],
            "mlp_norm.scale": [127.60546875, 128.4849090576172],
            "w_down": [-17.671783447265625, 128.77035494847223],
            "w_up": [-31.871246337890625, 253.42712562065572],
            "wk": [-6.0960540771484375, 30.573093231068924],
            "wo": [-4.4331817626953125, 128.55752749391831],
            "wq": [-2.06365966796875, 129.70625742245466],
            "wv": [-11.483139038085938, 31.191940151853487],
        },
        "token_gaps": [
            138.6148258447647, 3488.4583389759064, 5.241457939147949, 0.24820897355675697,
            9.126802757382393, 0.10706821829080582],
        "losses": [6.000661849975586, 6.344764709472656],
        "grad_norms": {
            "attn_norm.bias": [0.04332230985164642, 0.015168385580182076],
            "attn_norm.scale": [0.020286113023757935, 0.008460327982902527],
            "embed": [0.9510106444358826],
            "final_norm.bias": [0.015000694431364536],
            "final_norm.scale": [0.017274830490350723],
            "head": [0.0999603271484375],
            "mlp_norm.bias": [0.012559321708977222, 0.007433932274580002],
            "mlp_norm.scale": [0.0090025020763278, 0.00734310457482934],
            "w_down": [0.13239482045173645, 0.07858195900917053],
            "w_up": [0.09096227586269379, 0.05809984728693962],
            "wk": [0.07077927887439728, 0.018727514892816544],
            "wo": [0.10891225188970566, 0.07057090103626251],
            "wq": [0.07076307386159897, 0.02353808470070362],
            "wv": [0.1134052723646164, 0.07840387523174286],
        },
        "change_norms": {
            "attn_norm.bias": [0.011356578208506107, 0.013219933025538921],
            "attn_norm.scale": [0.0, 0.0],
            "embed": [0.11179386079311371],
            "final_norm.bias": [0.012693800963461399],
            "final_norm.scale": [0.0],
            "head": [0.20924320816993713],
            "mlp_norm.bias": [0.012230202555656433, 0.012542808428406715],
            "mlp_norm.scale": [0.0, 0.0],
            "w_down": [0.13842707872390747, 0.1396581381559372],
            "w_up": [0.13977542519569397, 0.1396300494670868],
            "wk": [0.048947326838970184, 0.04753921553492546],
            "wo": [0.09968025982379913, 0.09958776831626892],
            "wq": [0.09934338182210922, 0.09854523092508316],
            "wv": [0.049549371004104614, 0.050898678600788116],
        },
        "smoke_flops": [3495936.0, 1387008.0, 466944.0],
    },
}

REAL_FLOPS = {
    "minicpm-2b.batch-chat-unrolled": [1407170248704.0, 623233916928.0, 18613089792.0],
    "minicpm-2b.pretrain": [397278314496.0, 175328649216.0, 5080167936.0],
    "starcoder2-15b.code-complete-unrolled": [1733044469760.0, 762258653184.0, 21441282048.0],
}  # prefill 256, decode 256 + 112, train token at 2048

KERNEL_COST = {
    "minicpm-2b.pretrain": [
        [
            'streamed_matmul', [['bf16', [8, 2304]], ['bf16', [2304, 5888]]],
            [212336640.0, 26671104.0]],
        [
            'streamed_matmul', [['bf16', [4096, 2304]], ['bf16', [2304, 5760]]],
            [108716359680.0, 92602368.0]],
        [
            'streamed_matmul', [['bf16', [4096, 2304]], ['bf16', [2304, 122880]]],
            [2316885295104.0, 1590112768.0]],
        [
            'streamed_matmul', [['bf16', [4096, 5888]], ['bf16', [5888, 2304]]],
            [108716359680.0, 92602368.0]],
        [
            'flash_attention',
            [
                ['bf16', [2, 36, 2048, 64]], ['bf16', [2, 36, 2048, 64]],
                ['bf16', [2, 36, 2048, 64]]],
            [38673580032.0, 75497472.0]]],
    "starcoder2-15b.code-complete-unrolled": [
        [
            'streamed_matmul', [['bf16', [8, 6144]], ['bf16', [6144, 24576]]],
            [2415919104.0, 302481408.0]],
        [
            'streamed_matmul', [['bf16', [2048, 6144]], ['bf16', [6144, 512]]],
            [12884901888.0, 33554432.0]],
        [
            'streamed_matmul', [['bf16', [300, 6144]], ['bf16', [6144, 49152]]],
            [181193932800.0, 637157376.0]],
        [
            'flash_attention',
            [
                ['bf16', [1, 48, 2048, 128]], ['bf16', [1, 4, 2048, 128]],
                ['bf16', [1, 4, 2048, 128]]],
            [51564773376.0, 54525952.0]]],
}


def close(got, want):
    return got == pytest.approx(want, rel=1e-6, abs=1e-12)


@pytest.fixture(scope="module", params=list(CELLS))
def smoke(request):
    cell = tiny.cell(CELLS[request.param])
    cell.config.setdefault("optimizer", OPTIMIZER)
    return request.param, cell


def test_weights_survive_the_maps_into_and_out_of_the_program(smoke):
    name, cell = smoke
    c, fam = cell.config, cell.family
    w = weights.make(fam.layout(c), SEED, "bfloat16")
    back = fam.from_program(fam.to_program(c, dict(w), scanned=True))
    got = {k: [float(np.sum(np.asarray(v, np.float64))),
               float(np.sum(np.square(np.asarray(v, np.float64))))]
           for k, v in sorted(back.items())}
    assert list(got) == list(PINNED[name]["leaves"])
    for k, v in got.items():
        assert close(v, PINNED[name]["leaves"][k]), k


def test_token_gaps_on_fixed_tokens(smoke):
    name, cell = smoke
    c, fam = cell.config, cell.family
    w = weights.make(fam.layout(c), SEED, "bfloat16")
    toks = np.random.default_rng(0).integers(0, c["vocab_size"], (2, 24)).astype(np.int32)
    g, g8 = (np.asarray(x, np.float64) for x in fam.token_gaps(c, w, toks, fp8=True))
    idx = np.arange(1, g.size + 1).reshape(g.shape)
    got = [g.sum(), (g * idx).sum(), g.max(), g8.sum(), (g8 * idx).sum(), g8.max()]
    assert close([float(x) for x in got], PINNED[name]["token_gaps"])


def test_two_reference_train_steps(smoke):
    name, cell = smoke
    c, fam = cell.config, cell.family
    gen = Batches({"generator": "batches", "batch": 2, "seq_len": 32, "batches": 8}, SEED,
                  c["vocab_size"])
    ref = fam.train(c, lambda: weights.make(fam.layout(c), SEED, "bfloat16"),
                    [gen.batch_at(i) for i in range(2)])
    change = reference.change_norms(ref.pop("w"), weights.make(fam.layout(c), SEED, "bfloat16"),
                                    fam.stacked(c))
    assert close(ref["losses"], PINNED[name]["losses"])
    for got, want in ((ref["grad_norms"], PINNED[name]["grad_norms"]),
                      (change, PINNED[name]["change_norms"])):
        assert sorted(got) == sorted(want)
        for k in want:
            assert close(np.ravel(got[k]).tolist(), want[k]), k


def test_model_flops(smoke):
    name, cell = smoke
    c, fam = cell.config, cell.family
    got = [fam.prefill_flops(c, 24), fam.decode_flops(c, 24, 9), fam.train_token_flops(c, 32)]
    assert got == PINNED[name]["smoke_flops"]


@pytest.mark.parametrize("name", list(REAL_FLOPS))
def test_model_flops_at_the_real_sizes(name):
    cell = spec.cell(name)
    c, fam = cell.config, cell.family
    got = [fam.prefill_flops(c, 256), fam.decode_flops(c, 256, 112),
           fam.train_token_flops(c, 2048)]
    assert got == REAL_FLOPS[name]


@pytest.mark.parametrize("name", list(KERNEL_COST))
def test_kernel_costs(name):
    cell = spec.cell(name)
    kernels = spec.kernels()
    for kernel, operands, want in KERNEL_COST[name]:
        ops = [(t, tuple(shape)) for t, shape in operands]
        assert list(kernels[kernel].cost(ops, cell.config, cell.family)) == want
