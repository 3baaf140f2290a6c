"""MIGRATION.md must never name a symbol that doesn't exist.

The cheat sheet is the day-one surface for a reference user switching
over; a wrong name there is worse than no table. This test pins every
dotted module and symbol the document's "Here" column references.
"""

import importlib

import pytest

SYMBOLS = {
    "deeplearning4j_tpu.nn.conf.network": [
        "NeuralNetConfig", "MultiLayerConfiguration"],
    "deeplearning4j_tpu.nn.conf.inputs": [
        "ConvolutionalType", "RecurrentType", "convolutional"],
    "deeplearning4j_tpu.nn.graph": ["GraphBuilder", "ComputationGraph"],
    "deeplearning4j_tpu.nn.updaters": [
        "Sgd", "Adam", "AdaMax", "AdaDelta", "Nesterovs", "Nadam",
        "AdaGrad", "RmsProp", "NoOp"],
    "deeplearning4j_tpu.nn.layers": [
        "DenseLayer", "OutputLayer", "LossLayer", "ActivationLayer",
        "DropoutLayer", "EmbeddingLayer", "AutoEncoder",
        "ConvolutionLayer", "Convolution1DLayer", "Deconvolution2DLayer",
        "SeparableConvolution2DLayer", "BatchNormalization",
        "LocalResponseNormalization", "GlobalPoolingLayer",
        "SpaceToDepthLayer", "SpaceToBatchLayer", "LSTM", "GravesLSTM",
        "GravesBidirectionalLSTM", "SimpleRnn", "Bidirectional",
        "RnnOutputLayer", "RnnLossLayer", "LastTimeStep",
        "SubsamplingLayer", "Subsampling1DLayer", "Upsampling1DLayer",
        "Upsampling2DLayer", "ZeroPaddingLayer", "ZeroPadding1DLayer",
        "VariationalAutoencoder", "Yolo2OutputLayer",
        "CenterLossOutputLayer", "TransformerBlock", "MultiHeadAttention",
        "LayerNormalization", "MoETransformerBlock", "RMSNorm",
        "LoopedStack", "LoopedLMOutputLayer", "ShortConv", "GatedDeltaNet"],
    "deeplearning4j_tpu.nn.multilayer": ["MultiLayerNetwork"],
    "deeplearning4j_tpu.nn.listeners": [
        "ScoreIterationListener", "PerformanceListener",
        "EvaluativeListener", "TimeIterationListener",
        "ProfilerListener"],
    "deeplearning4j_tpu.nn.solvers": [
        "ConjugateGradient", "LBFGS", "backtrack_line_search"],
    "deeplearning4j_tpu.nn.earlystopping": ["EarlyStoppingTrainer"],
    "deeplearning4j_tpu.nn.transfer": [
        "TransferLearning", "TransferLearningGraph"],
    "deeplearning4j_tpu.utils.gradcheck": ["check_gradients"],
    "deeplearning4j_tpu.datasets.iterator": [
        "ArrayDataSetIterator", "AsyncDataSetIterator",
        "BenchmarkDataSetIterator", "MultipleEpochsIterator",
        "EarlyTerminationIterator", "ShardedDataSetIterator"],
    "deeplearning4j_tpu.datasets.fetchers": [],
    "deeplearning4j_tpu.datasets.records": [
        "csv_dataset", "CSVSequenceRecordReader", "sequence_dataset",
        "read_csv_records"],
    "deeplearning4j_tpu.datasets.images": ["image_dataset", "load_image"],
    "deeplearning4j_tpu.datasets.normalizers": [
        "NormalizerStandardize", "NormalizerMinMaxScaler",
        "ImagePreProcessingScaler"],
    "deeplearning4j_tpu.eval.classification": [
        "Evaluation", "EvaluationBinary", "ConfusionMatrix"],
    "deeplearning4j_tpu.eval.roc": ["ROC", "ROCBinary", "ROCMultiClass"],
    "deeplearning4j_tpu.eval.regression": ["RegressionEvaluation"],
    "deeplearning4j_tpu.eval.calibration": ["EvaluationCalibration"],
    "deeplearning4j_tpu.modelimport.keras": [],
    "deeplearning4j_tpu.nn.initializers": [],
    "deeplearning4j_tpu.modelimport.dl4j": [
        "write_multilayer_network", "restore_multilayer_network",
        "restore_computation_graph"],
    "deeplearning4j_tpu.models.zoo": [
        "init_pretrained", "restore_checkpoint"],
    "deeplearning4j_tpu.models": [
        "alexnet", "darknet19", "facenet_nn4_small2", "googlenet",
        "inception_resnet_v1", "lenet", "resnet50", "simple_cnn",
        "text_generation_lstm", "tiny_yolo", "vgg16", "vgg19",
        "transformer_lm", "looped_lm", "hybrid_moe_lm",
        "gated_delta_moe_lm"],
    "deeplearning4j_tpu.parallel": [
        "ParallelTrainer", "MeshSpec", "make_mesh"],
    "deeplearning4j_tpu.parallel.inference": ["ParallelInference"],
    "deeplearning4j_tpu.parallel.distributed": [
        "ParameterAveragingTrainingMaster", "SharedTrainingMaster",
        "initialize_distributed"],
    "deeplearning4j_tpu.parallel.pipeline_general": ["PipelinedNetwork",
                                                     "PipelinedGraph"],
    "deeplearning4j_tpu.parallel.composed": ["ComposedParallelLM"],
    "deeplearning4j_tpu.parallel.data_utils": [],
    "deeplearning4j_tpu.text.word2vec": ["Word2Vec", "SequenceVectors"],
    "deeplearning4j_tpu.text.paragraph_vectors": [],
    "deeplearning4j_tpu.text.glove": [],
    "deeplearning4j_tpu.text.languages": [
        "JapaneseTokenizerFactory", "ChineseTokenizerFactory",
        "KoreanTokenizerFactory"],
    "deeplearning4j_tpu.text.tokenization": [],
    "deeplearning4j_tpu.text.serializer": [],
    "deeplearning4j_tpu.text.bow": [],
    "deeplearning4j_tpu.graphlib.graph": [],
    "deeplearning4j_tpu.graphlib.walks": [],
    "deeplearning4j_tpu.graphlib.deepwalk": [],
    "deeplearning4j_tpu.graphlib.loader": [
        "load_undirected_edge_list", "load_weighted_edge_list",
        "load_graph"],
    "deeplearning4j_tpu.clustering.vptree": ["VPTree"],
    "deeplearning4j_tpu.clustering.kdtree": ["KDTree"],
    "deeplearning4j_tpu.clustering.server": [
        "NearestNeighborServer", "NearestNeighborClient"],
    "deeplearning4j_tpu.clustering.kmeans": [],
    "deeplearning4j_tpu.clustering.tsne": ["TSNE"],
    "deeplearning4j_tpu.ui.server": ["UIServer"],
    "deeplearning4j_tpu.ui.stats": ["StatsListener"],
    "deeplearning4j_tpu.ui.storage": ["RemoteStatsStorageRouter"],
    "deeplearning4j_tpu.ui.visualization": [
        "ConvolutionalIterationListener"],
    "deeplearning4j_tpu.ui.components": [],
    "deeplearning4j_tpu.utils.profiling": ["top_ops"],
    "deeplearning4j_tpu.utils.serialization": [
        "add_normalizer_to_model", "restore_normalizer"],
    "deeplearning4j_tpu.utils.dtypes": ["bf16_policy"],
    "deeplearning4j_tpu.mlpipeline": [
        "NeuralNetClassifier", "NeuralNetRegressor",
        "AutoEncoderTransformer"],
    "deeplearning4j_tpu.streaming": [],
    "deeplearning4j_tpu.nn.constraints": [],
    "deeplearning4j_tpu.nn.weightnoise": [],
    "deeplearning4j_tpu.nn.conf.memory": [],
}


@pytest.mark.parametrize("module", sorted(SYMBOLS))
def test_module_and_symbols_exist(module):
    mod = importlib.import_module(module)
    missing = [n for n in SYMBOLS[module] if not hasattr(mod, n)]
    assert not missing, f"{module}: {missing}"
