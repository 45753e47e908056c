"""dcgan64 — DCGAN at its published 64x64 widths (Radford et al. 2016,
Fig. 1; ngf = ndf = 128, nz = 100, batch 128, 5x5 kernels, Adam 2e-4 /
0.5 from the authors' 64x64 face/LSUN trainers): G projects the latent to
4x4x1024 and runs four 5x5 stride-2 transposed convs to 64x64x3; D runs
four 5x5 stride-2 convs of 128/256/512/1024 filters and a linear head.
Federated as dcgan-mnist: 5 clients x 4 devices, sorted_multi split.
"""
from repro.config import (DCGANConfig, FSLConfig, ModelConfig, OptimConfig,
                          ParallelConfig, RunConfig, ShapeConfig)


def config() -> RunConfig:
    return RunConfig(
        model=ModelConfig(
            name="dcgan64", family="dcgan",
            num_layers=4, d_model=0, num_heads=0, num_kv_heads=0,
            d_ff=0, vocab_size=0,
            dcgan=DCGANConfig(image_size=64, channels=3, latent_dim=100,
                              base_filters=128, conv_blocks=4,
                              gen_layout="radford"),
            source="[arXiv:1511.06434 Fig. 1; github.com/Newmu/dcgan_code]",
        ),
        parallel=ParallelConfig(fsdp=False, tensor_parallel=False,
                                sequence_parallel=False,
                                param_dtype="float32", compute_dtype="float32"),
        optim=OptimConfig(name="adam", lr=2e-4, beta1=0.5, beta2=0.999,
                          weight_decay=0.0, grad_clip=0.0),
        fsl=FSLConfig(num_clients=5, devices_per_client=4,
                      selection="sorted_multi", local_steps=1,
                      lan_latency_s=0.050, heterogeneity="paper"),
        shape=ShapeConfig(name="images64", seq_len=0, global_batch=128,
                          mode="train"),
    )
