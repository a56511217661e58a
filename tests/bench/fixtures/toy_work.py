"""Toy work counts for the harness's tests, to go with toy_reference: the
dense decoder's, with each layer's MLP GEMMs counted a second time."""
import work

MLP = ("up", "gate", "down")


def _second_mlp(model):
    return [g for g in work.layer_gemms(model)
            if g[0] in MLP] * model["n_layers"]


def step_gemm_least_time(model, tokens, peaks, bits=8):
    return work.step_gemm_least_time(model, tokens, peaks, bits) + sum(
        work.least_time(work.gemm_ops(k, n, tokens),
                        work.gemm_bytes(k, n, tokens, bits),
                        peaks["int8_ops"], peaks["hbm_bytes_per_s"])
        for _, k, n in _second_mlp(model))


def useful_least_time(model, tokens, context_sum, peaks):
    ops = sum(work.gemm_ops(k, n, tokens) for _, k, n in _second_mlp(model))
    return work.useful_least_time(model, tokens, context_sum, peaks) + \
        ops / peaks["int8_ops"]
